"""Xing4.0-29B-A4B pre-training, one chip's share, through the repo's public
entry points: ``models.transformer.build_joyai_pretrain`` under
``XingConfig`` (a residual stream four wide, every sublayer in a
manifold-constrained hyper-connection: ``hc_pre`` / ``hc_post``; latent
attention with YaRN's frequency table and softmax scale; a dense layer and
expert layers with sigmoid routing over 64 experts of which this chip holds 8
beside a shared expert) + AMP AdamW under ``layers.linear_lr_warmup`` + the
Executor; where the traffic says ``recompute``, under ``RecomputeOptimizer``
at the block outputs (it does not: the TPU compiler takes the plain step).

As in the LFM2 cell the traffic decides what the older cells leave to
``--seed`` (``traffic/lm_s4096_r64.json``): the weights are the model (the
startup program draws them from ``weights_seed``), ``--seed`` is the traffic
(it draws the token ids only), and the learning rate warms up from
``lr_start`` over ``lr_warmup_steps`` steps inside the program.

``correct`` is decided as the LFM2 cell decides it (``models/lfm2_8b_a1b.py``;
the helpers are ``trinity_mini``'s, ``olmoe_1b_7b``'s and
``smallthinker_21b_a3b``'s), everything after the window and after the memory
reading, and by one thing more: every hyper-connection's ``H_res``, as the
float32 and the AMP forward programs made it, has row and column sums of
1."""

import numpy as np

from .. import harness, xing4_flops
from . import _train
from . import olmoe_1b_7b as _olmoe
from . import smallthinker_21b_a3b as _small
from . import trinity_mini as _trinity

make_batch = _olmoe.make_batch

#: the kinds of leaf a gradient is judged by, and which entry of
#: :func:`gradient_difference`'s triples is held to the limit: 0 the kind's
#: leaves together, 1 its worst leaf
DECIDES = {"rest": 1, "experts": 0, "router": 0, "maps": 0}
KINDS = tuple(DECIDES)


def xing_config(config):
    from paddle_tpu.models import transformer as T
    a = config["assumed"]
    assert config["n_group"] == config["topk_group"] == 1, \
        "noaux_tc with one group is the path moe_ffn has"
    # what joyai_decoder_layer and latent_attention hold as constants
    assert (config["scoring_func"], config["norm_topk_prob"],
            a["rope_interleave"], config["n_shared_experts"]) == \
        ("sigmoid", True, True, 1)
    return T.XingConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layer=config["num_hidden_layers"],
        n_head=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        d_nope=config["qk_nope_head_dim"], d_rope=config["qk_rope_head_dim"],
        d_v=config["v_head_dim"], d_inner=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        n_experts=a["router_outputs"], top_k=config["num_experts_per_tok"],
        n_dense_layer=config["first_k_dense_replace"],
        n_mtp=config["num_nextn_predict_layers"],
        route_scale=config["routed_scaling_factor"],
        rms_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        n_held=config["n_routed_experts"], expert_offset=a["expert_offset"],
        hc_mult=config["hc_mult"],
        hc_sinkhorn_iters=config["hc_sinkhorn_iters"],
        hc_eps=config["hc_eps"],
        hc_res_clamp=(config["mhc_h_res_clamp_min"],
                      config["mhc_h_res_clamp_max"]),
        rope_scaling=config["rope_scaling"])


def reference_kw(cfg, q_block=512):
    s = cfg.rope_scaling
    return dict(
        n_head=cfg.n_head, d_nope=cfg.d_nope, d_rope=cfg.d_rope,
        d_v=cfg.d_v, top_k=cfg.top_k, eps=float(cfg.rms_eps),
        theta=float(cfg.rope_theta),
        yarn=(float(s["factor"]),
              float(s["original_max_position_embeddings"]),
              float(s["beta_fast"]), float(s["beta_slow"]),
              float(s["mscale"]), float(s["mscale_all_dim"])),
        route_scale=float(cfg.route_scale), hc_mult=int(cfg.hc_mult),
        hc_iters=int(cfg.hc_sinkhorn_iters), hc_eps=float(cfg.hc_eps),
        hc_clamp=tuple(float(v) for v in cfg.hc_res_clamp),
        expert_offset=int(cfg.expert_offset), q_block=int(q_block))


def reference_params(get, cfg, select_bias=True):
    """The program's parameters (``get(name)`` -> float32 array) in the
    layout of ``reference/xing4_29b_a4b.py``: the fused [d, r_q + r_kv +
    d_rope] down-projection split into ``w_qa`` and ``w_kva``, the fused
    gate-up weights into their two, each hyper-connection's three
    parameters as a group.  ``select_bias=False`` leaves the selection bias
    out (no gradient trains it: a tree of gradients has no such leaf)."""
    def hc(name):
        return {k: get(f"{name}.{k}") for k in ("phi", "alpha", "bias")}

    blocks = []
    for i in range(cfg.n_layer):
        p = f"dec_{i}"
        a = get(f"{p}.attn.a.w")
        blk = {"hc_attn": hc(f"{p}.hc_attn"), "hc_ffn": hc(f"{p}.hc_ffn"),
               "w_qa": a[:, :cfg.q_lora_rank], "w_kva": a[:, cfg.q_lora_rank:],
               "q_norm_w": get(f"{p}.attn.q_norm.w"),
               "kv_norm_w": get(f"{p}.attn.kv_norm.w"),
               "w_qb": get(f"{p}.attn.q_b.w"),
               "w_kvb": get(f"{p}.attn.kv_b.w"),
               "wo": get(f"{p}.attn.out.w"),
               "ln1_w": get(f"{p}.ln1.w"), "ln2_w": get(f"{p}.ln2.w")}
        if i < cfg.n_dense_layer:
            gu, f = get(f"{p}.ffn.gate_up.w"), cfg.d_inner
            blk.update(ffn_gate=gu[:, :f], ffn_up=gu[:, f:],
                       ffn_down=get(f"{p}.ffn.down.w"))
        else:
            gu, f = get(f"{p}.shared.gate_up.w"), cfg.d_expert
            blk.update(shared_gate=gu[:, :f], shared_up=gu[:, f:],
                       shared_down=get(f"{p}.shared.down.w"),
                       router_w=get(f"{p}.moe.router.w"),
                       gate_w=get(f"{p}.moe.gate.w"),
                       up_w=get(f"{p}.moe.up.w"),
                       down_w=get(f"{p}.moe.down.w"))
            if select_bias:
                blk["select_bias"] = get(f"{p}.moe.select_bias")
        blocks.append(blk)
    return {"wte": get("word_embedding"), "blocks": blocks,
            "final_norm_w": get("final_norm.w"), "head_w": get("lm_out.w")}


def reference_loss(reference, params, feed, cfg, hidden=None, q_block=512):
    """As ``lfm2_8b_a1b.reference_loss``: the reference's loss of ``feed``,
    its per-layer top-k choices and, with ``hidden``, per token the squared
    distance of a program's final-norm output from the reference's and the
    reference's own squared size."""
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


def reference_gradient(reference, params, feed, cfg, q_block):
    """``(loss, gradient)`` of the float32 reference on ``feed``, the
    gradient a tree like ``params`` without the selection bias, on the
    host."""
    import jax
    import jax.numpy as jnp
    kw = reference_kw(cfg, q_block)
    want, g_ref = jax.jit(jax.value_and_grad(
        lambda p, ids, labels: reference.loss(p, ids, labels, **kw)))(
            params, jnp.asarray(feed["src_ids"]),
            jnp.asarray(feed["lm_label"]))
    g_ref = jax.tree_util.tree_map(np.asarray, g_ref)
    for blk in g_ref["blocks"]:
        blk.pop("select_bias", None)
    return float(want), g_ref


#: how the cell compiles the reference: at the compiler's least effort for
#: the compiled code's speed.  The reference runs once (1.5 s at full effort)
#: and its float32 dots at ``highest`` are what the TPU's compiler spends its
#: time on: 107 s of a run's 360 at full effort, 28 s so
REFERENCE_COMPILER_OPTIONS = {"exec_time_optimization_effort": -1.0}


def reference_value_and_grad(reference, params, feed, cfg, q_block):
    """What the cell reads of the reference, from one program compiled once:
    ``(loss, top_e [L_expert, B*T, k], hidden [B, T, d], gradient)`` of the
    whole batch of ``feed``; ``jax.value_and_grad`` of the reference's loss
    with its experts a token and its final-norm output beside it, the
    gradient a tree like ``params`` without the selection bias, on the
    host."""
    import jax
    import jax.numpy as jnp
    kw = reference_kw(cfg, q_block)

    def loss(p, ids, labels):
        s = reference.batch_sums(p, ids, labels, **kw)
        return reference.loss_of_sums(s)["loss"], (s["top_e"], s["hidden"])

    args = (params, jnp.asarray(feed["src_ids"]),
            jnp.asarray(feed["lm_label"]))
    (want, (top_e, hidden)), g_ref = jax.jit(
        jax.value_and_grad(loss, has_aux=True)).lower(*args).compile(
            compiler_options=REFERENCE_COMPILER_OPTIONS)(*args)
    g_ref = jax.tree_util.tree_map(np.asarray, g_ref)
    for blk in g_ref["blocks"]:
        blk.pop("select_bias", None)
    return float(want), np.asarray(top_e), np.asarray(hidden, np.float32), \
        g_ref


def per_token_difference(got, want):
    """Per token the squared distance of a program's final-norm output
    ``got`` from the reference's ``want`` and the reference's own squared
    size, as ``olmoe_1b_7b.hidden_difference`` takes them."""
    got, want = (np.asarray(v, np.float64).reshape(-1, v.shape[-1])
                 for v in (got, want))
    return np.square(got - want).sum(-1), np.square(want).sum(-1)


def kind_of(name):
    """The kind a leaf of the reference's tree is judged with."""
    if "['hc_attn']" in name or "['hc_ffn']" in name:
        return "maps"
    if "router_w" in name:
        return "router"
    return "experts" if name.endswith(
        ("['gate_w']", "['up_w']", "['down_w']")) else "rest"


def gradient_difference(g_ref, grads):
    """``smallthinker_21b_a3b.gradient_difference`` with a fourth kind:
    ``maps``, the hyper-connections' ``phi``, ``alpha`` and ``bias`` (their
    gradients come through 20 Sinkhorn-Knopp iterations and a sum over every
    token and lane of the stream, and are small beside a weight's: judged
    with each other, their leaves together, the worst printed); ``router``
    (``router_w``) and ``experts`` (``gate_w``, ``up_w``, ``down_w``) by
    their leaves together for the reason given there, ``rest`` (latent
    attention, norms, dense and shared FFN, embedding, head) by its worst
    leaf.  ``{kind: (together, worst, leaf), "all": together over every
    leaf}``."""
    import jax
    sums = {k: [0.0, 0.0] for k in KINDS}
    worst = {k: (0.0, "") for k in KINDS}
    leaves = [(jax.tree_util.keystr(path), (got, ref)) for (path, ref), got in
              zip(jax.tree_util.tree_flatten_with_path(g_ref)[0],
                  jax.tree_util.tree_leaves(grads))]
    for name, d2, r2 in _squares_by_leaf(
            leaves, lambda got, ref: (got.astype(np.float64) - ref, ref)):
        kind = kind_of(name)
        sums[kind][0] += d2
        sums[kind][1] += r2
        e = (d2 / r2) ** 0.5 if r2 > 0 else float(d2 > 0)
        if not e <= worst[kind][0]:             # a NaN is the worst
            worst[kind] = (e, name)
    out = {k: ((d2 / max(r2, 1e-300)) ** 0.5,) + worst[k]
           for k, (d2, r2) in sums.items()}
    out["all"] = (sum(d2 for d2, _ in sums.values())
                  / max(sum(r2 for _, r2 in sums.values()), 1e-300)) ** 0.5
    return out


def stochastic_off(h_res, n):
    """The largest distance from 1 of a row sum or a column sum of the ``n x
    n`` maps in ``h_res`` (arrays [.., n * n], one a hyper-connection)."""
    worst = 0.0
    for h in h_res:
        m = np.asarray(h, np.float64).reshape(-1, n, n)
        worst = max(worst, float(np.abs(m.sum(-1) - 1.0).max()),
                    float(np.abs(m.sum(-2) - 1.0).max()))
        if not np.isfinite(m).all():
            return float("inf")
    return worst


#: elements of a leaf that the host compares at a time: float64 temporaries of
#: 8 MB stay in a core's cache, where those of a whole 66 M-element leaf are
#: paged in anew by every line of ``reference.adamw`` (28 s a leaf against 3)
CHUNK = 1 << 20


def _squares_by_leaf(leaves, pair):
    """``[(name, |a|^2, |b|^2)]`` in float64 for ``leaves`` = ``[(name,
    arrays)]``, where ``pair(*chunks)`` makes ``(a, b)`` of the same
    :data:`CHUNK` elements of each of a leaf's arrays: the chunks on
    the host's cores side by side, their squares summed by the leaf."""
    import os
    from concurrent.futures import ThreadPoolExecutor
    flat = {name: [np.asarray(a).ravel() for a in arrays]
            for name, arrays in leaves}

    def part(task):
        name, lo = task
        a, b = pair(*(v[lo:lo + CHUNK] for v in flat[name]))
        return name, float(np.sum(np.square(a, dtype=np.float64))), \
            float(np.sum(np.square(b, dtype=np.float64)))

    sums = {name: [0.0, 0.0] for name, _ in leaves}
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        for name, a2, b2 in pool.map(part, [
                (name, lo) for name, v in flat.items()
                for lo in range(0, v[0].size, CHUNK)]):
            sums[name][0] += a2
            sums[name][1] += b2
    return [(name, a2, b2) for name, (a2, b2) in sums.items()]


def _replayed_update(built, traffic, first_feed, grads, reference):
    """``smallthinker_21b_a3b._replayed_update`` (its docstring says what is
    compared and why), with the host's part sized for 759 M parameters: the
    reference's AdamW (``reference.adamw``, float64 numpy) and the two sums
    of squares run over :data:`CHUNK` elements of a leaf at a time
    (:func:`_squares_by_leaf`).  The same numbers to rounding: AdamW is
    element-wise, and the sums of squares add up."""
    import jax
    import jax.numpy as jnp
    scope = built["scope"]
    names = [v.name for v in built["parameters"]]
    before = {n: np.array(scope.find_var(n), np.float32) for n in names}
    at = int(traffic["lr_warmup_steps"]) // 2
    counter = scope.find_var(_small.LR_COUNTER)
    scope.set_var(_small.LR_COUNTER, jnp.asarray(at - 1, counter.dtype))
    feed = {k: jax.device_put(v) for k, v in first_feed.items()}
    built["exe"].run(built["program"], feed=feed, fetch_list=[built["loss"]],
                     scope=scope)
    sched = (float(traffic["learning_rate"]), int(traffic["lr_warmup_steps"]),
             float(traffic["lr_start"]))
    rates = [reference.warmup_rate(step, *sched) for step in (0, at)]

    def pair(b, after, g):
        want = reference.adamw(b, [(rate, g) for rate in rates],
                               traffic["weight_decay"]) - b
        return after.astype(np.float64) - b - want, want

    off2 = size2 = 0.0
    worst = (0.0, "")
    for n, d2, r2 in _squares_by_leaf(
            [(n, (before[n], np.asarray(scope.find_var(n), np.float32),
                  grads[n])) for n in names], pair):
        off2, size2 = off2 + d2, size2 + r2
        e = (d2 / r2) ** 0.5 if r2 > 0 else float(d2 > 0)
        if not e <= worst[0]:                   # a NaN is the worst
            worst = (e, n)
    return {"all": (off2 / max(size2, 1e-300)) ** 0.5, "worst": worst,
            "rate": rates[1]}


def _forward_program(cfg, seq, scope, amp):
    """The same model, forward only, over the parameters of ``scope``; the
    names to fetch: loss and final-norm output, each expert layer's
    ExpertLoad and TopExperts, each hyper-connection's HRes."""
    import paddle_tpu as pt
    from paddle_tpu.framework import Program, program_guard, scope_guard
    from paddle_tpu.models import transformer as T
    main = Program()
    with scope_guard(scope), program_guard(main, Program()):
        _, parts, loss = T.build_joyai_pretrain(cfg, seq)
    if amp:
        pt.amp.enable(main)
    ops = main.global_block().ops
    tops = [op.outputs["TopExperts"][0] for op in ops
            if op.type == "moe_ffn"]
    maps = [op.outputs["HRes"][0] for op in ops if op.type == "hc_pre"]
    return main, [loss.name, parts["hidden"].name], \
        [v.name for v in parts["expert_load"]], tops, maps


def _run_forward(exe, scope, fwd, feed, cfg):
    """One forward program's fetches, sorted: ``(loss, hidden, loads, tops
    [L, S, k], the H_res maps' distance from doubly stochastic)``."""
    main, heads, loads, tops, maps = fwd
    got = exe.run(main, feed=feed, fetch_list=heads + loads + tops + maps,
                  scope=scope)
    rest = got[len(heads):]
    n_l, n_t = len(loads), len(tops)
    return (float(np.asarray(got[0])), got[1],
            [np.asarray(v) for v in rest[:n_l]],
            np.stack([np.asarray(v).reshape(-1, cfg.top_k)
                      for v in rest[n_l:n_l + n_t]]),
            stochastic_off(rest[n_l + n_t:], cfg.hc_mult))


def build_train(config, traffic, seed, chips, on_chip):
    import paddle_tpu as pt
    from paddle_tpu import layers, optimizer as opt
    from paddle_tpu.framework import (Program, Scope, program_guard,
                                      scope_guard)
    from paddle_tpu.models import transformer as T

    cfg = xing_config(config)
    seq = traffic["seq_len"]
    batch = traffic["batch_per_chip"] * chips
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        # ISSUE 45: no recomputation where the TPU compiler takes the plain
        # step (it does: the traffic file's recompute_why); the fallback is
        # RecomputeOptimizer at the block outputs (the whole widened
        # streams) and nothing finer
        checkpoints = [] if traffic.get("recompute") else None
        _, _, loss = T.build_joyai_pretrain(cfg, seq,
                                            checkpoints=checkpoints)
        rate = float(traffic["learning_rate"])
        adamw = opt.AdamWOptimizer(
            learning_rate=layers.linear_lr_warmup(
                rate, int(traffic["lr_warmup_steps"]),
                float(traffic["lr_start"]), rate),
            weight_decay=traffic["weight_decay"])
        stepper = adamw
        if checkpoints:
            stepper = opt.RecomputeOptimizer(adamw)
            stepper._set_checkpoints(checkpoints)
        pt.amp.decorate(stepper).minimize(loss)
        exe = _train.executor(on_chip)
        # the weights are the model: the startup program's seed is the
        # traffic's, and --seed draws the ids alone
        w_seed = int(traffic["weights_seed"])
        exe.run(startup, scope=scope, seed=harness.exe_seed(w_seed))
    rng = _train.rng_of(seed)
    ring = [make_batch(rng, cfg, batch, seq) for _ in range(traffic["ring"])]
    return {
        "exe": exe, "scope": scope, "cfg": cfg,
        "program": _train.maybe_data_parallel(main, loss, chips),
        "loss": loss.name, "ring": ring, "batch": batch,
        "parameters": main.all_parameters(),
        "flops_per_sample": xing4_flops.train_flops_per_sample(config, seq),
        # for the checks after the window (``trinity_mini._initial_state``
        # reads "startup" and "seed"): the startup program makes the initial
        # state again from the weights' seed, and a step from zeroed moments
        # leaves (1 - beta1) x its gradient in each parameter's first moment
        "startup": startup, "seed": w_seed, "beta1": adamw._beta1,
        "moment1": {name: v.name for name, v in
                    adamw._accumulators["moment1"].items()},
    }


def check_before_window(config, traffic, built, seed, reference, chips):
    """Nothing before the window, as in the Trinity-Mini cell and for its
    reason: a second program beside the step's state would raise the memory
    peak the cell reports."""
    return {"ok": True,
            "detail": "no check before the window: the float32 forward "
            "program, the step's own first loss and its first gradient are "
            "compared with the reference after the window and after the "
            "memory reading, from the initial state the startup program "
            "makes again from the weights' seed"}


def decide(tol, r):
    """The cell's decision over its readings ``r`` (floats under the names
    below; :func:`check_first_loss` reads them from the program,
    ``tools/smallthinker_tolerance_probe.py --cell xing4`` from the
    reference computed in bf16 in the program's place): ``(ok, [the limits a
    reading exceeds])``.  A reading that is not a number exceeds its
    limit."""
    held = [("relative", r["f32_loss"]),
            ("top_k_differ_share", r["f32_share"]),
            ("hidden_relative", r["f32_hidden"]),
            ("h_res_sums", max(r["f32_h_res"], r["first_h_res"])),
            ("first_hidden_relative", r["first_hidden"]),
            ("replayed_update_relative", r["update"])] + [
        (f"first_gradient_{k}_relative", r[f"gradient_{k}"])
        for k in KINDS + ("all",)]
    if tol.get("first_training_loss_relative") is not None:
        held.append(("first_training_loss_relative",
                     max(r["first_loss"], r["first_forward"])))
    failed = [name for name, v in held if not v <= tol[name]]
    if not r["replay"] <= 1e-6:
        failed.append("replay")
    if not r["dropless"]:
        failed.append("dropless")
    return not failed, failed


def check_first_loss(config, traffic, built, first_loss, first_feed,
                     reference):
    """Every comparison of the cell, after the window and after the memory
    reading, each from the initial state the startup program makes again,
    all on the timed sequence (the first batch of the ring):

    * the routing as the window left it, into the routed-rows counter
      (``trinity_mini._routing_at_close``);
    * the timed AMP AdamW step itself, once more
      from the initial state: its loss is the one it fetched first in this
      run, and **its gradient**, every parameter's, read from the first
      moment, against ``jax.grad`` of the float32 reference: ``hc_pre_grad``
      and ``hc_post_grad`` (Sinkhorn-Knopp differentiated through its 20
      iterations), the two-width flash backward, the table-form
      ``rope_grad``, ``moe_ffn_grad``'s held path and the AdamW op at the
      timed sizes; the hyper-connections' leaves judged as a kind of their
      own;
    * the timed step once more half-way up the warm-up: every parameter's
      change against the reference's AdamW in float64
      (:func:`_replayed_update`);
    * the float32 forward program (no AMP, matmuls at ``highest``) against
      the reference: loss, each token's 4 of 64 experts in every expert
      layer, the final-norm output over the tokens whose experts are the
      reference's; and every hyper-connection's ``H_res``: row and column
      sums against 1 (a Sinkhorn-Knopp cut short or run in bf16 shows there
      before it shows in a loss);
    * the step's first loss against the reference's, and a forward-only AMP
      program's final-norm output, ``H_res``, ExpertLoad and experts a
      token.

    The reference's loss, experts a token, final-norm output and gradient
    come from one program compiled once (:func:`reference_value_and_grad`),
    and the host's float64 comparisons run by the chunk on its cores
    (:func:`_squares_by_leaf`): a run has to end well inside the driver's
    360 s, and its log line "checks after the window" says where these
    seconds went.  :func:`decide` holds the readings to the configuration's
    limits."""
    import time
    import jax
    import jax.numpy as jnp
    cfg, scope, exe = built["cfg"], built["scope"], built["exe"]
    tol = config["loss_tolerance"]
    phases, t_last = [], [time.perf_counter()]

    def mark(name):
        now = time.perf_counter()
        phases.append(f"{name} {now - t_last[0]:.1f}s")
        t_last[0] = now

    seq = traffic["seq_len"]
    q_block = traffic.get("reference_q_block", 512)
    fwd_amp = _forward_program(cfg, seq, scope, amp=True)
    # ``trinity_mini._routing_at_close`` with every fetch the comparison
    # further down asks for, so that one compiled program serves both
    load_close = _run_forward(exe, scope, fwd_amp, first_feed, cfg)[2]
    _trinity._count_loads(cfg, load_close)
    mark("AMP forward program at the window's weights")
    replayed, grads = _trinity._replayed_first_step(built, first_feed)
    mark("first step once more")
    # the selection bias is a parameter no gradient trains: not a leaf here
    update = _replayed_update(
        dict(built, parameters=[v for v in built["parameters"]
                                if v.name in built["moment1"]]),
        traffic, first_feed, grads, reference)
    mark("replayed update")

    _trinity._initial_state(built)
    _trinity._erase(scope, keep={v.name for v in built["parameters"]})

    # the float32 forward program and the forward-only AMP program on the
    # timed sequence
    fwd32 = _forward_program(cfg, seq, scope, amp=False)
    with jax.default_matmul_precision("highest"):
        got32, hidden32, load32, top32, h_res32 = _run_forward(
            exe, scope, fwd32, first_feed, cfg)
    mark("float32 forward program")
    got, hidden, load, top, h_res = _run_forward(
        exe, scope, fwd_amp, first_feed, cfg)
    mark("AMP forward program")

    # the reference on the initial weights: the fused weights go, the
    # reference's stay
    params = reference_params(
        lambda name: jnp.asarray(scope.find_var(name), jnp.float32), cfg)
    _trinity._erase(scope)
    want, ref_top, ref_hidden, g_ref = reference_value_and_grad(
        reference, params, first_feed, cfg, q_block)
    del params
    mark("reference loss and gradient")
    differ32 = _olmoe.tokens_that_differ(top32, ref_top)
    r = {"f32_loss": _train.rel_err(got32, want),
         "f32_share": float(differ32.mean()),
         "f32_hidden": _olmoe.hidden_difference(
             per_token_difference(hidden32, ref_hidden), ~differ32),
         "f32_h_res": h_res32, "first_h_res": h_res,
         "first_hidden": _olmoe.hidden_difference(
             per_token_difference(hidden, ref_hidden))}
    g_off = gradient_difference(
        g_ref, reference_params(grads.__getitem__, cfg, select_bias=False))
    del g_ref, grads, hidden, hidden32, ref_hidden
    mark("gradient difference")
    harness.log("checks after the window: " + ", ".join(phases))

    rows = top.shape[1] * cfg.top_k
    differ = int(_olmoe.tokens_that_differ(top, ref_top).sum())
    r.update(
        first_loss=_train.rel_err(first_loss, want),
        first_forward=_train.rel_err(got, first_loss),
        replay=_train.rel_err(replayed, first_loss),
        dropless=all(int(v.sum()) == rows
                     for v in load + load_close + load32),
        update=update["worst"][0], gradient_all=g_off["all"],
        **{f"gradient_{k}": g_off[k][DECIDES[k]] for k in KINDS})
    ok, failed = decide(tol, r)
    t_loss = tol.get("first_training_loss_relative")

    def held(loads_):
        return [int(v[cfg.expert_offset:cfg.expert_offset + cfg.n_held].sum())
                for v in loads_]

    return {"ok": ok, "readings": r, "detail":
            f"float32 forward loss {got32:.6f} vs reference {want:.6f} on "
            f"{built['batch']} sequences: relative difference "
            f"{r['f32_loss']:.2e} (tolerance {tol['relative']}); tokens "
            f"whose top-{cfg.top_k} differs from the reference's in some "
            f"layer: {int(differ32.sum())} of {differ32.size}, a share of "
            f"{r['f32_share']:.2e} (tolerance {tol['top_k_differ_share']}); "
            f"final-norm output over the others {r['f32_hidden']:.2e} from "
            f"the reference's (tolerance {tol['hidden_relative']}); every "
            f"H_res's row and column sums within {h_res32:.2e} of 1 in the "
            f"float32 program and {r['first_h_res']:.2e} in the AMP program "
            f"(tolerance {tol['h_res_sums']}); first training loss "
            f"{float(first_loss):.6f} (AMP) vs reference {want:.6f} "
            f"(float32): relative difference {r['first_loss']:.2e}, the "
            f"forward-only AMP program reads {got:.6f} "
            f"({r['first_forward']:.2e} from the step's) (tolerance "
            f"{t_loss or 'none: printed, not decided by'}), its final-norm "
            f"output {r['first_hidden']:.2e} from the reference's "
            f"(tolerance {tol['first_hidden_relative']}); the first step "
            f"once more from the startup program's state reads "
            f"{replayed:.6f} ({r['replay']:.2e} from the run's first), its "
            f"gradient against jax.grad of the reference: " + "".join(
                f"{k}: worst leaf {g_off[k][1]:.3e} at {g_off[k][2]}, its "
                f"leaves together {g_off[k][0]:.3e} (tolerance "
                f"{tol[f'first_gradient_{k}_relative']} on the "
                f"{'worst leaf' if DECIDES[k] else 'leaves together'}), "
                for k in KINDS) +
            f"all leaves together {g_off['all']:.3e} (tolerance "
            f"{tol['first_gradient_all_relative']}); the step once more at "
            f"the rate {update['rate']:.3g} (half-way up the warm-up): the "
            f"parameters' change against the reference's AdamW, worst leaf "
            f"{update['worst'][0]:.3e} at {update['worst'][1]}, all leaves "
            f"together {update['all']:.3e} (tolerance "
            f"{tol['replayed_update_relative']} on the worst leaf; a state "
            f"left unchanged reads 1); ExpertLoad sums to {rows} in every "
            f"layer: "
            f"{r['dropless']}, rows on the {cfg.n_held} held experts "
            f"{held(load)} at the initial weights and {held(load_close)} as "
            f"the window left them; tokens whose top-{cfg.top_k} differs "
            f"from the reference's under AMP: {differ} of {top.shape[1]}; "
            f"limits exceeded: {failed or 'none'}"}
