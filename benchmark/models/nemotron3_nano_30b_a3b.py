"""NVIDIA-Nemotron-3-Nano-30B-A3B in pre-training, one chip's share of an
expert-parallel-16 stage, through the repo's public entry points:
``models.transformer.build_nemotron_h_pretrain`` (ONE sublayer a block by the
row's ``hybrid_override_pattern``: Mamba-2 mixers through ``ssd_scan``, the
biased ``short_conv`` and ``gated_rms_norm``; position-free grouped-query
attention at 32 over 2 heads through the flash kernels; sigmoid top-6 of 128
un-gated ReLU^2 experts of which this chip holds 8, beside a wide shared
expert) + AMP AdamW under ``layers.linear_lr_warmup`` + the Executor; where
the traffic says ``recompute``, under ``RecomputeOptimizer`` at the block
boundaries.

The traffic is Solar-Open2's and Ling's file as it stands
(``traffic/lm_s8192_r64.json``): the weights are the model (the startup
program draws them from ``weights_seed``), ``--seed`` is the traffic (it draws
the token ids only), and the learning rate warms up from ``lr_start`` over
``lr_warmup_steps`` steps inside the program.

``correct`` is decided as the Ling cell decides it, with the helpers that
cell's adapter imports (``xing4_29b_a4b``'s for the reference program and the
host's float64 comparisons, ``trinity_mini``'s, ``olmoe_1b_7b``'s) and that
adapter's own two that read nothing of its model, everything
after the window and after the memory reading: nothing of the reference
compiles or runs before the window opens.  Two kinds of gradient leaf beside
the usual three: ``mamba`` (a Mamba-2 block's own parameters) and
``attention`` (the attention block's four projections)."""

import numpy as np

from .. import harness, nemotron3_flops
from . import _train
from . import ling3_flash_vl as _ling
from . import olmoe_1b_7b as _olmoe
from . import trinity_mini as _trinity
from . import xing4_29b_a4b as _xing

make_batch = _olmoe.make_batch

#: the kinds of leaf a gradient is judged by, and which entry of
#: :func:`gradient_difference`'s triples is held to the limit: 0 the kind's
#: leaves together, 1 its worst leaf
DECIDES = {"rest": 1, "experts": 0, "router": 0, "mamba": 1, "attention": 1}
KINDS = tuple(DECIDES)

#: the reference's names of a Mamba-2 block's own parameters (the kind
#: ``mamba``): what reaches the loss only through ``ssd_scan_grad``, the
#: biased ``short_conv_grad`` and the gated norm; its input and output
#: projections are judged with ``rest``
MAMBA_LEAVES = ("conv_w", "conv_b", "a_log", "d_skip", "dt_bias", "gnorm_w")
#: and the attention block's projections (the kind ``attention``): what
#: reaches the loss through the flash kernels at 16 query heads a K/V head
ATTENTION_LEAVES = ("wq", "wk", "wv", "wo")


def nemotron_config(config):
    from paddle_tpu.models import transformer as T
    a = config["assumed"]
    h, p = config["mamba_num_heads"], config["mamba_head_dim"]
    # what nemotron_h_block, mamba2_mixer and relu2_ffn hold as constants of
    # this family
    assert (config["mlp_hidden_act"], config["mamba_hidden_act"],
            config["use_conv_bias"], config["use_bias"], config["mlp_bias"],
            config["attention_bias"], config["mamba_proj_bias"],
            config["norm_topk_prob"], config["n_group"],
            config["topk_group"], config["n_shared_experts"],
            config["tie_word_embeddings"], config["residual_in_fp32"],
            config["sliding_window"]) == \
        ("relu2", "silu", True, False, False, False, False, True, 1, 1, 1,
         False, False, None)
    assert config["norm_eps"] == config["layer_norm_epsilon"]
    assert config["moe_intermediate_size"] == config["intermediate_size"]
    assert len(config["hybrid_override_pattern"]) == \
        config["num_hidden_layers"]
    assert a["d_inner"] == h * p
    return T.NemotronHConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        pattern=config["hybrid_override_pattern"], n_mamba_head=h,
        d_mamba_head=p, n_group=config["n_groups"],
        d_state=config["ssm_state_size"], conv_taps=config["conv_kernel"],
        chunk=config["chunk_size"], n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"], d_head=config["head_dim"],
        d_expert=config["moe_intermediate_size"],
        d_shared=config["moe_shared_expert_intermediate_size"],
        n_experts=a["router_outputs"], top_k=config["num_experts_per_tok"],
        route_scale=config["routed_scaling_factor"],
        rms_eps=config["norm_eps"], n_held=config["n_routed_experts"],
        expert_offset=a["expert_offset"])


def reference_kw(cfg, q_block=512, scan_block=128):
    return dict(groups=cfg.n_group, d_state=cfg.d_state, d_head=cfg.d_head,
                top_k=cfg.top_k, eps=float(cfg.rms_eps),
                route_scale=float(cfg.route_scale),
                expert_offset=int(cfg.expert_offset), q_block=int(q_block),
                scan_block=int(scan_block))


def reference_params(get, cfg, select_bias=True):
    """The program's parameters (``get(name)`` -> float32 array) in the
    layout of ``reference/nemotron3_nano_30b_a3b.py``: the attention block's
    fused ``[d, (Hq + 2 Hkv) dh]`` projection split into Q, K and V; the
    rest under the reference's names.  ``select_bias=False`` leaves the
    selection bias out (no gradient trains it: a tree of gradients has no
    such leaf)."""
    dq, dkv = cfg.n_head * cfg.d_head, cfg.n_kv_head * cfg.d_head
    blocks = []
    for i, kind in enumerate(cfg.pattern):
        p = f"dec_{i}"
        blk = {"norm_w": get(f"{p}.norm.w")}
        if kind == "M":
            blk.update(w_in=get(f"{p}.mamba.in_proj.w"),
                       conv_w=get(f"{p}.mamba.conv.filter"),
                       conv_b=get(f"{p}.mamba.conv.bias"),
                       a_log=get(f"{p}.mamba.A_log"),
                       d_skip=get(f"{p}.mamba.D"),
                       dt_bias=get(f"{p}.mamba.dt_bias"),
                       gnorm_w=get(f"{p}.mamba.norm.w"),
                       w_out=get(f"{p}.mamba.out.w"))
        elif kind == "*":
            w = get(f"{p}.attn.qkv.w")
            blk.update(wq=w[:, :dq], wk=w[:, dq:dq + dkv],
                       wv=w[:, dq + dkv:], wo=get(f"{p}.attn.out.w"))
        else:
            blk.update(router_w=get(f"{p}.moe.router.w"),
                       shared_up=get(f"{p}.shared.up.w"),
                       shared_down=get(f"{p}.shared.down.w"),
                       up_w=get(f"{p}.moe.up.w"),
                       down_w=get(f"{p}.moe.down.w"))
            if select_bias:
                blk["select_bias"] = get(f"{p}.moe.select_bias")
        blocks.append(blk)
    return {"wte": get("word_embedding"), "blocks": blocks,
            "final_norm_w": get("final_norm.w"), "head_w": get("lm_out.w")}


def reference_loss(reference, params, feed, cfg, hidden=None, q_block=512):
    """As ``xing4_29b_a4b.reference_loss`` (what ``tools/
    smallthinker_tolerance_probe.py --cell nemotron3`` reads the control by):
    the reference's loss of ``feed``, its per-layer top-k choices and, with
    ``hidden``, per token the squared distance of a final-norm output from
    the reference's and the reference's own squared size."""
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
            d2, r2 = _xing.per_token_difference(hidden[i:i + 1], want)
            off2.append(d2)
            size2.append(r2)
        total = s if total is None else \
            jax.tree_util.tree_map(jnp.add, total, s)
    return (float(reference.loss_of_sums(total)["loss"]),
            np.concatenate(tops, axis=1),
            (np.concatenate(off2), np.concatenate(size2))
            if hidden is not None else None)


def reference_gradient(reference, params, feed, cfg, q_block):
    """``(loss, gradient)`` of the reference on ``feed`` in ``params``'s own
    precision, the gradient a tree like ``params`` without the selection
    bias, on the host."""
    import jax
    import jax.numpy as jnp
    kw = reference_kw(cfg, q_block)
    want, g_ref = jax.jit(jax.value_and_grad(
        lambda p, ids, labels: reference.loss(p, ids, labels, **kw)))(
            params, jnp.asarray(feed["src_ids"]),
            jnp.asarray(feed["lm_label"]))
    g_ref = jax.tree_util.tree_map(
        lambda a: np.asarray(a, np.float32), g_ref)
    for blk in g_ref["blocks"]:
        blk.pop("select_bias", None)
    return float(want), g_ref


def reference_value_and_grad(reference, params, feed, cfg, q_block,
                             scan_block=128):
    """What the cell reads of the reference, from one program compiled once
    (at the compiler's least effort for the compiled code's speed, as
    ``xing4_29b_a4b.reference_value_and_grad``): ``(loss, top_e [L, B*T, k],
    hidden [B, T, d], gradient)`` of the whole batch of ``feed``, the
    gradient a tree like ``params`` without the selection bias, on the
    host."""
    import jax
    import jax.numpy as jnp
    kw = reference_kw(cfg, q_block, scan_block)

    def loss(p, ids, labels):
        s = reference.batch_sums(p, ids, labels, **kw)
        return reference.loss_of_sums(s)["loss"], (s["top_e"], s["hidden"])

    args = (params, jnp.asarray(feed["src_ids"]),
            jnp.asarray(feed["lm_label"]))
    (want, (top_e, hidden)), g_ref = jax.jit(
        jax.value_and_grad(loss, has_aux=True)).lower(*args).compile(
            compiler_options=_xing.REFERENCE_COMPILER_OPTIONS)(*args)
    g_ref = jax.tree_util.tree_map(np.asarray, g_ref)
    for blk in g_ref["blocks"]:
        blk.pop("select_bias", None)
    return float(want), np.asarray(top_e), np.asarray(hidden, np.float32), \
        g_ref


def kind_of(name):
    """The kind a leaf of the reference's tree is judged with."""
    if name.endswith(tuple(f"['{k}']" for k in MAMBA_LEAVES)):
        return "mamba"
    if name.endswith(tuple(f"['{k}']" for k in ATTENTION_LEAVES)):
        return "attention"
    if "router_w" in name:
        return "router"
    return "experts" if name.endswith(("['up_w']", "['down_w']")) else "rest"


def gradient_difference(g_ref, grads):
    """``ling3_flash_vl.gradient_difference`` with this model's kinds:
    ``mamba``, a Mamba-2 block's own parameters (the filter and its bias,
    ``A_log``, ``D``, ``dt_bias``, the gated norm's scale: what reaches the
    loss only through ``ssd_scan_grad``, the biased ``short_conv_grad`` and
    ``gated_rms_norm``'s vjp), and ``attention``, the attention block's four
    projections (through the flash backward at 16 query heads a K/V head),
    each held to its WORST leaf, its leaves together printed: judged
    together the small leaves (``A_log``'s 64 numbers) would weigh nothing
    beside a 27M-number projection; ``router`` and ``experts`` by their
    leaves together (one chip's share of the experts sees a sixteenth of the
    rows), ``rest`` (the Mamba blocks' input and output projections, the
    block norms, the shared expert, embedding, head) by its worst leaf.
    ``{kind: (together, worst, leaf), "all": together over every leaf}``."""
    import jax
    sums = {k: [0.0, 0.0] for k in KINDS}
    worst = {k: (0.0, "") for k in KINDS}
    leaves = [(jax.tree_util.keystr(path), (got, ref)) for (path, ref), got in
              zip(jax.tree_util.tree_flatten_with_path(g_ref)[0],
                  jax.tree_util.tree_leaves(grads))]
    for name, d2, r2 in _xing._squares_by_leaf(
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


def _forward_program(cfg, seq, scope, amp):
    """The same model, forward only, over the parameters of ``scope``; the
    names to fetch: loss and final-norm output, each layer's ExpertLoad and
    TopExperts."""
    import paddle_tpu as pt
    from paddle_tpu.framework import Program, program_guard, scope_guard
    from paddle_tpu.models import transformer as T
    main = Program()
    with scope_guard(scope), program_guard(main, Program()):
        _, parts, loss = T.build_nemotron_h_pretrain(cfg, seq)
    if amp:
        pt.amp.enable(main)
    tops = [op.outputs["TopExperts"][0] for op in main.global_block().ops
            if op.type == "moe_ffn"]
    return main, [loss.name, parts["hidden"].name], \
        [v.name for v in parts["expert_load"]], tops


_run_forward = _ling._run_forward


def build_train(config, traffic, seed, chips, on_chip):
    import paddle_tpu as pt
    from paddle_tpu import layers, optimizer as opt
    from paddle_tpu.framework import (Program, Scope, program_guard,
                                      scope_guard)
    from paddle_tpu.models import transformer as T

    cfg = nemotron_config(config)
    seq = traffic["seq_len"]
    batch = traffic["batch_per_chip"] * chips
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        # the traffic's rule: RecomputeOptimizer at the blocks' boundaries
        # (the first block's input and the nine block outputs) and nothing
        # finer; the plain step where the traffic says recompute false
        checkpoints = [] if traffic.get("recompute") else None
        _, _, loss = T.build_nemotron_h_pretrain(cfg, seq,
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
            # a segment behind its gradient: the compiler otherwise runs
            # the blocks again side by side, a set of expert buffers each
            stepper._set_checkpoints(checkpoints, after_gradient=True)
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
        "flops_per_sample": nemotron3_flops.train_flops_per_sample(
            config, seq),
        # for the checks after the window (``trinity_mini._initial_state``
        # reads "startup" and "seed"): the startup program makes the initial
        # state again from the weights' seed, and a step from zeroed moments
        # leaves (1 - beta1) x its gradient in each parameter's first moment
        "startup": startup, "seed": w_seed, "beta1": adamw._beta1,
        "moment1": {name: v.name for name, v in
                    adamw._accumulators["moment1"].items()},
    }


check_before_window = _ling.check_before_window


def decide(tol, r):
    """The cell's decision over its readings ``r`` (floats under the names
    below; :func:`check_first_loss` reads them from the program,
    ``tools/smallthinker_tolerance_probe.py --cell nemotron3`` from the
    reference computed in bf16 in the program's place): ``(ok, [the limits a
    reading exceeds])``.  A reading that is not a number exceeds its limit."""
    held = [("relative", r["f32_loss"]),
            ("top_k_differ_share", r["f32_share"]),
            ("hidden_relative", r["f32_hidden"]),
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
      (``trinity_mini._count_loads``);
    * the timed AMP AdamW step itself, once more from the initial state: its
      loss is the one it fetched first in this run, and **its gradient**,
      every parameter's, read from the first moment, against ``jax.grad`` of
      the float32 reference, whose Mamba-2 blocks run token by token:
      ``ssd_scan_grad``, the biased ``short_conv_grad``, ``gated_rms_norm``,
      the flash backward at 16 query heads a K/V head, ``moe_ffn_grad``'s
      held path over un-gated experts and the AdamW op at the timed sizes;
      the Mamba blocks' own parameters and the attention block's projections
      judged as kinds of their own;
    * the timed step once more half-way up the warm-up: every parameter's
      change against the reference's AdamW in float64
      (``xing4_29b_a4b._replayed_update``);
    * the float32 forward program (no AMP, matmuls at ``highest``) against
      the reference: loss, each token's 6 of 128 experts in every expert
      block, the final-norm output over the tokens whose experts are the
      reference's;
    * the step's first loss against the reference's, and a forward-only AMP
      program's final-norm output, ExpertLoad and experts a token.

    The reference's loss, experts a token, final-norm output and gradient
    come from one program compiled once (:func:`reference_value_and_grad`),
    and the host's float64 comparisons run by the chunk on its cores: a run
    has to end well inside the driver's 360 s, and its log line "checks
    after the window" says where these seconds went.  :func:`decide` holds
    the readings to the configuration's limits."""
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
    scan_block = traffic.get("reference_scan_block", 128)
    fwd_amp = _forward_program(cfg, seq, scope, amp=True)
    load_close = _run_forward(exe, scope, fwd_amp, first_feed, cfg)[2]
    _trinity._count_loads(cfg, load_close)
    mark("AMP forward program at the window's weights")
    replayed, grads = _trinity._replayed_first_step(built, first_feed)
    mark("first step once more")
    # the selection bias is a parameter no gradient trains: not a leaf here
    update = _xing._replayed_update(
        dict(built, parameters=[v for v in built["parameters"]
                                if v.name in built["moment1"]]),
        traffic, first_feed, grads, reference)
    mark("replayed update")

    _trinity._initial_state(built)
    _trinity._erase(scope, keep={v.name for v in built["parameters"]})

    fwd32 = _forward_program(cfg, seq, scope, amp=False)
    with jax.default_matmul_precision("highest"):
        got32, hidden32, load32, top32 = _run_forward(
            exe, scope, fwd32, first_feed, cfg)
    mark("float32 forward program")
    got, hidden, load, top = _run_forward(exe, scope, fwd_amp, first_feed,
                                          cfg)
    mark("AMP forward program")

    # the reference on the initial weights: the fused weights go, the
    # reference's stay
    params = reference_params(
        lambda name: jnp.asarray(scope.find_var(name), jnp.float32), cfg)
    _trinity._erase(scope)
    want, ref_top, ref_hidden, g_ref = reference_value_and_grad(
        reference, params, first_feed, cfg, q_block, scan_block)
    del params
    mark("reference loss and gradient")
    differ32 = _olmoe.tokens_that_differ(top32, ref_top)
    r = {"f32_loss": _train.rel_err(got32, want),
         "f32_share": float(differ32.mean()),
         "f32_hidden": _olmoe.hidden_difference(
             _xing.per_token_difference(hidden32, ref_hidden), ~differ32),
         "first_hidden": _olmoe.hidden_difference(
             _xing.per_token_difference(hidden, ref_hidden))}
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
            f"the reference's (tolerance {tol['hidden_relative']}); first "
            f"training loss {float(first_loss):.6f} (AMP) vs reference "
            f"{want:.6f} (float32): relative difference "
            f"{r['first_loss']:.2e}, the forward-only AMP program reads "
            f"{got:.6f} ({r['first_forward']:.2e} from the step's) "
            f"(tolerance {t_loss or 'none: printed, not decided by'}), its "
            f"final-norm output {r['first_hidden']:.2e} from the "
            f"reference's (tolerance {tol['first_hidden_relative']}); the "
            f"first step once more from the startup program's state reads "
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
            f"layer: {r['dropless']}, rows on the {cfg.n_held} held experts "
            f"{held(load)} at the initial weights and {held(load_close)} as "
            f"the window left them; tokens whose top-{cfg.top_k} differs "
            f"from the reference's under AMP: {differ} of {top.shape[1]}; "
            f"limits exceeded: {failed or 'none'}"}
