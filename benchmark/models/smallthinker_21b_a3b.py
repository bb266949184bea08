"""SmallThinker-21BA3B-Instruct pre-training, one chip's share, through the
repo's public entry points: ``models.transformer.build_smallthinker_pretrain``
(a router that reads the layer's input before attention, ReLU-gated experts
of which this chip holds 8 of 64, window-4096 layers with rotary and
position-free full layers over 28 query / 4 K/V heads, the fused head) + AMP
AdamW under ``layers.linear_lr_warmup`` + the Executor.

The traffic decides two things the other cells leave to ``--seed``
(``traffic/lm_s16384.json``): the weights are the model (the startup program
draws them from ``weights_seed``) and ``--seed`` is the traffic (it draws the
token ids only); the learning rate warms up from ``lr_start`` over
``lr_warmup_steps`` steps inside the program.

``correct`` is decided as the Trinity-Mini cell decides it
(``models/trinity_mini.py``, whose comparisons this file uses), everything
after the window and after the memory reading; and, because the schedule's
first rate is 0 and the first step therefore moves no weight, by one more
step at a rate that does (:func:`_replayed_update`)."""

import numpy as np

from .. import harness, smallthinker_flops
from . import _train
from . import olmoe_1b_7b as _olmoe
from . import trinity_mini as _trinity

make_batch = _olmoe.make_batch


def smallthinker_config(config):
    from paddle_tpu.models import transformer as T
    a = config["assumed"]
    return T.SmallThinkerConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layer=config["num_hidden_layers"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"], d_head=config["head_dim"],
        d_expert=config["moe_ffn_hidden_size"],
        n_experts=a["router_outputs"],
        top_k=config["moe_num_active_primary_experts"],
        window=config["sliding_window_size"],
        sliding_window_layout=config["sliding_window_layout"],
        rope_layout=config["rope_layout"], rms_eps=config["rms_norm_eps"],
        rope_theta=config["rope_theta"],
        n_held=config["moe_num_primary_experts"],
        expert_offset=a["expert_offset"])


def reference_kw(cfg, q_block=512):
    return dict(windows=tuple(int(cfg.window) if w else 0
                              for w in cfg.sliding_window_layout),
                rotary=tuple(bool(r) for r in cfg.rope_layout),
                n_head=cfg.n_head, n_kv_head=cfg.n_kv_head,
                d_head=cfg.d_head, top_k=cfg.top_k, eps=float(cfg.rms_eps),
                theta=float(cfg.rope_theta),
                expert_offset=int(cfg.expert_offset), q_block=int(q_block))


def reference_params(get, cfg):
    """The program's parameters (``get(name)`` -> float32 array) in the
    layout of ``reference/smallthinker_21b_a3b.py``: the fused [d, H dh + 2
    Hkv dh] projection split into Q, K and V."""
    dq, dkv = cfg.n_head * cfg.d_head, cfg.n_kv_head * cfg.d_head
    blocks = []
    for i in range(cfg.n_layer):
        p = f"dec_{i}"
        qkv = get(f"{p}.attn.qkv.w")
        blocks.append({
            "ln1_w": get(f"{p}.ln1.w"), "router_w": get(f"{p}.moe.router.w"),
            "wq": qkv[:, :dq], "wk": qkv[:, dq:dq + dkv],
            "wv": qkv[:, dq + dkv:], "wo": get(f"{p}.attn.out.w"),
            "ln2_w": get(f"{p}.ln2.w"), "gate_w": get(f"{p}.moe.gate.w"),
            "up_w": get(f"{p}.moe.up.w"), "down_w": get(f"{p}.moe.down.w")})
    return {"wte": get("word_embedding"), "blocks": blocks,
            "final_norm_w": get("final_norm.w"), "head_w": get("lm_out.w")}


def reference_loss(reference, params, feed, cfg, hidden=None, q_block=512):
    """As ``trinity_mini.reference_loss``: the reference's loss of ``feed``,
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
    gradient a tree like ``params`` on the host."""
    import jax
    import jax.numpy as jnp
    kw = reference_kw(cfg, q_block)
    want, g_ref = jax.jit(jax.value_and_grad(
        lambda p, ids, labels: reference.loss(p, ids, labels, **kw)))(
            params, jnp.asarray(feed["src_ids"]),
            jnp.asarray(feed["lm_label"]))
    return float(want), jax.tree_util.tree_map(np.asarray, g_ref)


#: which entry of :func:`gradient_difference`'s triples is held to the limit
DECIDES = {"rest": 1, "experts": 0, "router": 0}


def gradient_difference(g_ref, grads):
    """``|g - g_ref| / |g_ref|`` of the reference's parameters (``grads``:
    another computation's, in the reference's layout), three kinds of leaf
    held apart as ``trinity_mini.gradient_difference`` holds them and for
    its reason (bf16 activations choose another expert for a share of the
    tokens, and a router's gradient and an expert's jump with the choice):
    ``router`` (``router_w``), ``experts`` (``gate_w``, ``up_w``, ``down_w``
    and ``ln2_w``: in this block the post-attention norm is read by the
    experts alone, so its scale's gradient comes through their rows like
    theirs) and ``rest`` (attention, the input norms, embedding, final norm,
    head).  Each kind two ways: its worst single leaf with that leaf's name,
    and the kind's leaves **together** (``sqrt(sum |g - g_ref|^2 / sum
    |g_ref|^2)``).  :data:`DECIDES` says which the cell decides by: ``rest``
    by its worst leaf (a dK or dV summed wrongly is one leaf's fault), the
    routers and the experts by their leaves together, the worst being
    printed: one chip's share of a layer's experts may get a hundredth of
    the rows (1067 of 98304 in one layer of the fixed weights), and that
    layer's router and experts are then a few rows' noise over a small
    gradient.  ``{kind: (together, worst, leaf), "all": together over every
    leaf}``."""
    import jax
    sums = {k: [0.0, 0.0] for k in ("router", "experts", "rest")}
    worst = {k: (0.0, "") for k in sums}
    for (path, ref), got in zip(
            jax.tree_util.tree_flatten_with_path(g_ref)[0],
            jax.tree_util.tree_leaves(grads)):
        name = jax.tree_util.keystr(path)
        ref = np.asarray(ref, np.float64)
        d2 = float(np.sum(np.square(np.asarray(got, np.float64) - ref)))
        r2 = float(np.sum(np.square(ref)))
        kind = ("router" if "router_w" in name else "experts"
                if name.endswith(("['gate_w']", "['up_w']", "['down_w']",
                                  "['ln2_w']")) else "rest")
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


def _forward_program(cfg, seq, scope, amp):
    """The same model, forward only, over the parameters of ``scope``; the
    names to fetch: loss, final-norm output, each layer's ExpertLoad and
    TopExperts."""
    import paddle_tpu as pt
    from paddle_tpu.framework import Program, program_guard, scope_guard
    from paddle_tpu.models import transformer as T
    main = Program()
    with scope_guard(scope), program_guard(main, Program()):
        _, parts, loss = T.build_smallthinker_pretrain(cfg, seq, is_test=True)
    if amp:
        pt.amp.enable(main)
    tops = [op.outputs["TopExperts"][0] for op in main.global_block().ops
            if op.type == "moe_ffn"]
    return main, [loss.name, parts["hidden"].name], \
        [v.name for v in parts["expert_load"]], tops


def build_train(config, traffic, seed, chips, on_chip):
    import paddle_tpu as pt
    from paddle_tpu import layers, optimizer as opt
    from paddle_tpu.framework import (Program, Scope, program_guard,
                                      scope_guard)
    from paddle_tpu.models import transformer as T

    cfg = smallthinker_config(config)
    seq = traffic["seq_len"]
    batch = traffic["batch_per_chip"] * chips
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        # the step fits without recomputation (13.66 GB by the compiler):
        # ISSUE 38's one fallback was not needed and is not built
        assert not traffic["recompute"]
        _, _, loss = T.build_smallthinker_pretrain(cfg, seq)
        rate = float(traffic["learning_rate"])
        adamw = opt.AdamWOptimizer(
            learning_rate=layers.linear_lr_warmup(
                rate, int(traffic["lr_warmup_steps"]),
                float(traffic["lr_start"]), rate),
            weight_decay=traffic["weight_decay"])
        pt.amp.decorate(adamw).minimize(loss)
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
        "flops_per_sample": smallthinker_flops.train_flops_per_sample(
            config, seq),
        # for the checks after the window (``trinity_mini._initial_state``
        # reads "startup" and "seed"): the startup program makes the initial
        # state again from the weights' seed, and a step from zeroed moments
        # leaves (1 - beta1) x its gradient in each parameter's first moment
        "startup": startup, "seed": w_seed, "beta1": adamw._beta1,
        "moment1": {name: v.name for name, v in
                    adamw._accumulators["moment1"].items()},
    }


#: the schedule's step counter in the scope (``layers.learning_rate_
#: scheduler._decay_step_counter``): ``increment``ed, then read, every step
LR_COUNTER = "@LR_DECAY_COUNTER@"


def _replayed_update(built, traffic, first_feed, grads, reference):
    """What the first step cannot show, its rate being ``lr_start`` 0: that
    the optimizer moves the weights as AdamW does.  Right after
    ``trinity_mini._replayed_first_step`` (the scope holds what that step
    left: the initial parameters, unmoved, and one step's moments) the timed
    step runs once more on the same sequence with the schedule's counter set
    half-way up the warm-up, at half the traffic's rate; every parameter's
    change ``dp`` against the reference's (``reference.adamw``: two steps
    from zeroed moments at the rates ``reference.warmup_rate`` gives for
    step 0 and for that step, both on ``grads``, the gradient the first
    step left in the first moment, which the gradient limits hold to
    ``jax.grad`` of the reference; Kingma & Ba's betas and epsilon, which
    are ``AdamWOptimizer``'s defaults): ``|dp - dp_ref| / |dp_ref|``.  A state
    left unchanged reads 1.  ``{"all": the leaves together, "worst": (the
    worst leaf's reading, its name), "rate": the reference's rate}``."""
    import jax
    import jax.numpy as jnp
    scope = built["scope"]
    names = [v.name for v in built["parameters"]]
    before = {n: np.array(scope.find_var(n), np.float32) for n in names}
    at = int(traffic["lr_warmup_steps"]) // 2
    counter = scope.find_var(LR_COUNTER)
    scope.set_var(LR_COUNTER, jnp.asarray(at - 1, counter.dtype))
    feed = {k: jax.device_put(v) for k, v in first_feed.items()}
    built["exe"].run(built["program"], feed=feed, fetch_list=[built["loss"]],
                     scope=scope)
    sched = (float(traffic["learning_rate"]), int(traffic["lr_warmup_steps"]),
             float(traffic["lr_start"]))
    rates = [reference.warmup_rate(step, *sched) for step in (0, at)]
    off2 = size2 = 0.0
    worst = (0.0, "")
    for n in names:
        want = reference.adamw(
            before[n], [(rate, grads[n]) for rate in rates],
            traffic["weight_decay"]) - before[n]
        got = np.asarray(scope.find_var(n), np.float64) - before[n]
        d2, r2 = float(np.sum(np.square(got - want))), \
            float(np.sum(np.square(want)))
        off2, size2 = off2 + d2, size2 + r2
        e = (d2 / r2) ** 0.5 if r2 > 0 else float(d2 > 0)
        if not e <= worst[0]:                   # a NaN is the worst
            worst = (e, n)
    return {"all": (off2 / max(size2, 1e-300)) ** 0.5, "worst": worst,
            "rate": rates[1]}


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


def check_first_loss(config, traffic, built, first_loss, first_feed,
                     reference):
    """Every comparison of the cell, after the window and after the memory
    reading, each from the initial state the startup program makes again,
    all on the timed sequence (the first batch of the ring):

    * the routing as the window left it, into the routed-rows counter
      (``trinity_mini._routing_at_close``);
    * the timed AMP AdamW step itself, once more from the initial state: its
      loss is the one it fetched first in this run, and **its gradient**,
      every parameter's, read from the first moment, against ``jax.grad`` of
      the float32 reference: the window and group-of-7 flash backward,
      ``moe_ffn_grad``'s held path with the ReLU gate and the router's own
      input, and the AdamW op at the timed sizes;
    * the timed step once more at half the traffic's rate: every parameter's
      change against the reference's AdamW (:func:`_replayed_update`);
    * the float32 forward program (no AMP, matmuls at ``highest``) against
      the reference: loss, each token's 6 of 64 experts in every layer, the
      final-norm output over the tokens whose experts are the reference's;
    * the step's first loss against the reference's, and a forward-only AMP
      program's final-norm output, ExpertLoad and experts a token."""
    import jax
    import jax.numpy as jnp
    cfg, scope, exe = built["cfg"], built["scope"], built["exe"]
    tol = config["loss_tolerance"]
    seq = traffic["seq_len"]
    q_block = traffic.get("reference_q_block", 512)
    fwd_amp = _forward_program(cfg, seq, scope, amp=True)
    load_close = _trinity._routing_at_close(built, fwd_amp, first_feed)
    replayed, grads = _trinity._replayed_first_step(built, first_feed)
    update = _replayed_update(built, traffic, first_feed, grads, reference)

    _trinity._initial_state(built)
    _trinity._erase(scope, keep={v.name for v in built["parameters"]})

    def initial(name):
        return jnp.asarray(scope.find_var(name), jnp.float32)

    # the float32 forward program on the timed sequence
    main, heads, loads, tops = _forward_program(cfg, seq, scope, amp=False)
    with jax.default_matmul_precision("highest"):
        got32, hidden, *rest = exe.run(
            main, feed=first_feed, fetch_list=heads + loads + tops,
            scope=scope)
    params = reference_params(initial, cfg)
    want, ref_top, per_token = reference_loss(
        reference, params, first_feed, cfg, hidden=hidden, q_block=q_block)
    f32 = _olmoe.before_window_verdict(
        tol, np.asarray(got32), want, per_token,
        np.stack([np.asarray(v).reshape(-1, cfg.top_k)
                  for v in rest[len(loads):]]), ref_top,
        [np.asarray(v) for v in rest[:len(loads)]], built["batch"])
    del hidden, rest, per_token

    # the forward-only AMP program on the same sequence
    main, heads, loads, tops = fwd_amp
    got, hidden, *rest = exe.run(
        main, feed=first_feed, fetch_list=heads + loads + tops, scope=scope)
    _, _, per_token = reference_loss(
        reference, params, first_feed, cfg, hidden=hidden, q_block=q_block)
    hidden_off = _olmoe.hidden_difference(per_token)
    load = [np.asarray(v) for v in rest[:len(loads)]]
    top = np.stack([np.asarray(v).reshape(-1, cfg.top_k)
                    for v in rest[len(loads):]])
    del hidden, rest, per_token

    # the step's gradient: the fused weights go, the reference's stay
    _trinity._erase(scope)
    want_g, g_ref = reference_gradient(reference, params, first_feed, cfg,
                                       q_block)
    g_off = gradient_difference(g_ref, reference_params(grads.__getitem__,
                                                        cfg))
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
              and all(g_off[k][DECIDES[k]] <= t_grad[k]
                      for k in ("rest", "experts", "router"))
              and g_off["all"] <= t_grad["all"]
              and update["worst"][0] <= tol["replayed_update_relative"])
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
                f"{k}: worst leaf {g_off[k][1]:.3e} at {g_off[k][2]}, its "
                f"leaves together {g_off[k][0]:.3e} (tolerance "
                f"{t_grad[k]} on the "
                f"{'worst leaf' if DECIDES[k] else 'leaves together'}), "
                for k in ("rest", "experts", "router")) +
            f"all leaves together {g_off['all']:.3e} (tolerance "
            f"{t_grad['all']}); the step once more at the rate "
            f"{update['rate']:.3g} (half-way up the warm-up): the "
            f"parameters' change against the reference's AdamW, worst leaf "
            f"{update['worst'][0]:.3e} at {update['worst'][1]}, all leaves "
            f"together {update['all']:.3e} (tolerance "
            f"{tol['replayed_update_relative']} on the worst leaf; a state "
            f"left unchanged reads 1); "
            f"ExpertLoad sums to {rows} in every layer: {dropless}, rows on "
            f"the {cfg.n_held} held experts {held(load)} at the initial "
            f"weights and {held(load_close)} as the window left them; "
            f"tokens whose top-{cfg.top_k} differs from the reference's: "
            f"{differ} of {top.shape[1]}"}
