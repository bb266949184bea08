"""LFM2-8B-A1B pre-training, one chip's share, through the repo's public
entry points: ``models.transformer.build_lfm2_pretrain`` (gated
short-convolution operators beside grouped-query attention at 64-wide heads
with a per-head QK-norm and rotary, sigmoid routing over 32 experts of which
this chip holds 8, one table for the embedding and the fused head) + AMP
AdamW under ``layers.linear_lr_warmup`` + the Executor.

As in the SmallThinker cell the traffic decides two things the older cells
leave to ``--seed`` (``traffic/lm_s16384_r64.json``): the weights are the
model (the startup program draws them from ``weights_seed``) and ``--seed``
is the traffic (it draws the token ids only); the learning rate warms up from
``lr_start`` over ``lr_warmup_steps`` steps inside the program.

``correct`` is decided as the SmallThinker cell decides it
(``models/smallthinker_21b_a3b.py``, whose comparisons this file uses),
everything after the window and after the memory reading: the float32
forward program, the timed step's own first loss and first gradient against
the reference, and one more step half-way up the warm-up against the
reference's AdamW."""

import numpy as np

from .. import harness, lfm2_flops
from . import _train
from . import olmoe_1b_7b as _olmoe
from . import smallthinker_21b_a3b as _small
from . import trinity_mini as _trinity

make_batch = _olmoe.make_batch
DECIDES = _small.DECIDES


def lfm2_config(config):
    from paddle_tpu.models import transformer as T
    a = config["assumed"]
    return T.Lfm2Config(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layer=config["num_hidden_layers"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"], d_head=a["head_dim"],
        d_inner=config["intermediate_size"],
        d_expert=config["moe_intermediate_size"],
        n_experts=a["router_outputs"], top_k=config["num_experts_per_tok"],
        n_dense_layer=config["num_dense_layers"],
        layer_types=config["layer_types"], conv_taps=config["conv_L_cache"],
        route_scale=config["routed_scaling_factor"],
        rms_eps=config["norm_eps"], rope_theta=config["rope_theta"],
        n_held=config["num_experts"], expert_offset=a["expert_offset"])


def reference_kw(cfg, q_block=512):
    return dict(n_head=cfg.n_head, n_kv_head=cfg.n_kv_head,
                d_head=cfg.d_head, top_k=cfg.top_k, eps=float(cfg.rms_eps),
                theta=float(cfg.rope_theta),
                route_scale=float(cfg.route_scale),
                expert_offset=int(cfg.expert_offset), q_block=int(q_block))


def reference_params(get, cfg, select_bias=True):
    """The program's parameters (``get(name)`` -> float32 array) in the
    layout of ``reference/lfm2_8b_a1b.py``: the fused [d, H dh + 2 Hkv dh]
    projection split into Q, K and V, the fused gate-up weight into its
    two; no head: the reference reads the table too.  ``select_bias=False``
    leaves the selection bias out (no gradient trains it: a tree of
    gradients has no such leaf)."""
    dq, dkv = cfg.n_head * cfg.d_head, cfg.n_kv_head * cfg.d_head
    blocks = []
    for i in range(cfg.n_layer):
        p = f"dec_{i}"
        blk = {"ln1_w": get(f"{p}.ln1.w"), "ln2_w": get(f"{p}.ln2.w")}
        if cfg.layer_types[i] == "full_attention":
            qkv = get(f"{p}.attn.qkv.w")
            blk.update(wq=qkv[:, :dq], wk=qkv[:, dq:dq + dkv],
                       wv=qkv[:, dq + dkv:],
                       q_norm_w=get(f"{p}.attn.q_norm.w"),
                       k_norm_w=get(f"{p}.attn.k_norm.w"),
                       wo=get(f"{p}.attn.out.w"))
        else:
            blk.update(in_w=get(f"{p}.conv.in_proj.w"),
                       conv_w=get(f"{p}.conv.filter"),
                       out_w=get(f"{p}.conv.out_proj.w"))
        if i < cfg.n_dense_layer:
            gu, f = get(f"{p}.ffn.gate_up.w"), cfg.d_inner
            blk.update(ffn_gate=gu[:, :f], ffn_up=gu[:, f:],
                       ffn_down=get(f"{p}.ffn.down.w"))
        else:
            blk.update(router_w=get(f"{p}.moe.router.w"),
                       gate_w=get(f"{p}.moe.gate.w"),
                       up_w=get(f"{p}.moe.up.w"),
                       down_w=get(f"{p}.moe.down.w"))
            if select_bias:
                blk["select_bias"] = get(f"{p}.moe.select_bias")
        blocks.append(blk)
    return {"wte": get("word_embedding"), "blocks": blocks,
            "final_norm_w": get("final_norm.w")}


def reference_loss(reference, params, feed, cfg, hidden=None, q_block=512):
    """As ``smallthinker_21b_a3b.reference_loss``: the reference's loss of
    ``feed``, its per-layer top-k choices and, with ``hidden``, per token the
    squared distance of a program's final-norm output from the reference's
    and the reference's own squared size."""
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
    gradient a tree like ``params`` without the selection bias, on the host;
    the table's leaf holds the sum of the lookup's and the head's."""
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


#: ``smallthinker_21b_a3b.gradient_difference`` over two trees without the
#: selection bias: ``router`` (``router_w``) and ``experts`` (``gate_w``,
#: ``up_w``, ``down_w`` and ``ln2_w``, the norm that in an expert layer only
#: the router and the experts read; the dense layer's is judged with them)
#: each held to its leaves together, ``rest`` (the conv operators, attention,
#: the dense FFN, the input norms, the final norm and the table, whose leaf
#: is the sum of its two readers') to its worst leaf
gradient_difference = _small.gradient_difference


def _forward_program(cfg, seq, scope, amp):
    """The same model, forward only, over the parameters of ``scope``; the
    names to fetch: loss, final-norm output, each expert layer's ExpertLoad
    and TopExperts."""
    import paddle_tpu as pt
    from paddle_tpu.framework import Program, program_guard, scope_guard
    from paddle_tpu.models import transformer as T
    main = Program()
    with scope_guard(scope), program_guard(main, Program()):
        _, parts, loss = T.build_lfm2_pretrain(cfg, seq, is_test=True)
    if amp:
        pt.amp.enable(main)
    tops = [op.outputs["TopExperts"][0] for op in main.global_block().ops
            if op.type == "moe_ffn"]
    return main, [loss.name, parts["hidden"].name], \
        [v.name for v in parts["expert_load"]], tops


def table_reads(program, table="word_embedding"):
    """The forward ops of ``program`` that read ``table``: the lookup and,
    the embeddings tied, the head (what recomputation emits again of them
    is the same two readers a second time, and not counted)."""
    return [op.type for op in program.global_block().ops
            if table in op.input_arg_names()
            and not op.type.endswith("_grad")
            and not op.attrs.get("recomputed")
            and op.attrs.get("op_role") not in ("backward", "optimize")]


def build_train(config, traffic, seed, chips, on_chip):
    import paddle_tpu as pt
    from paddle_tpu import layers, optimizer as opt
    from paddle_tpu.framework import (Program, Scope, program_guard,
                                      scope_guard)
    from paddle_tpu.models import transformer as T

    cfg = lfm2_config(config)
    seq = traffic["seq_len"]
    batch = traffic["batch_per_chip"] * chips
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        # the traffic runs without recomputation (the step fits); ISSUE 40's
        # one fallback, checkpoints at the block outputs and nothing finer
        checkpoints = [] if traffic.get("recompute") else None
        _, _, loss = T.build_lfm2_pretrain(cfg, seq, checkpoints=checkpoints)
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
        "flops_per_sample": lfm2_flops.train_flops_per_sample(config, seq),
        "table_reads": table_reads(main),
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
      the float32 reference: ``short_conv_grad``, the group-of-4 flash
      backward at 64-wide heads, ``moe_ffn_grad``'s held path, the table's
      leaf as the sum of the lookup's and the head's, and the AdamW op at
      the timed sizes;
    * the timed step once more at half the traffic's rate: every parameter's
      change against the reference's AdamW
      (``smallthinker_21b_a3b._replayed_update``);
    * the float32 forward program (no AMP, matmuls at ``highest``) against
      the reference: loss, each token's 4 of 32 experts in every expert
      layer, the final-norm output over the tokens whose experts are the
      reference's;
    * the step's first loss against the reference's, and a forward-only AMP
      program's final-norm output, ExpertLoad and experts a token;
    * the table has two readers in the timed program, the lookup and the
      head (tied embeddings)."""
    import jax
    import jax.numpy as jnp
    cfg, scope, exe = built["cfg"], built["scope"], built["exe"]
    tol = config["loss_tolerance"]
    seq = traffic["seq_len"]
    q_block = traffic.get("reference_q_block", 512)
    fwd_amp = _forward_program(cfg, seq, scope, amp=True)
    load_close = _trinity._routing_at_close(built, fwd_amp, first_feed)
    replayed, grads = _trinity._replayed_first_step(built, first_feed)
    # the selection bias is a parameter no gradient trains: not a leaf here
    update = _small._replayed_update(
        dict(built, parameters=[v for v in built["parameters"]
                                if v.name in built["moment1"]]),
        traffic, first_feed, grads, reference)

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
    g_off = gradient_difference(
        g_ref, reference_params(grads.__getitem__, cfg, select_bias=False))
    del g_ref, grads

    rows = top.shape[1] * cfg.top_k
    differ = int(_olmoe.tokens_that_differ(top, ref_top).sum())
    err = _train.rel_err(first_loss, want)
    err_fwd = _train.rel_err(np.asarray(got), first_loss)
    err_replay = _train.rel_err(replayed, first_loss)
    # the two AMP losses decide only where the configuration gives them a
    # limit (this one's does not, and says why: first_training_loss_reason)
    t_loss = tol.get("first_training_loss_relative")
    t_grad = {k: tol[f"first_gradient_{k}_relative"]
              for k in ("rest", "experts", "router", "all")}
    dropless = all(int(v.sum()) == rows for v in load + load_close)
    readers = built["table_reads"]
    tied = readers == ["lookup_table", "fused_lm_head_ce"]

    def held(loads_):
        return [int(v[cfg.expert_offset:cfg.expert_offset + cfg.n_held].sum())
                for v in loads_]

    ok = bool(f32["ok"] and np.isfinite(err)
              and (t_loss is None or max(err, err_fwd) <= t_loss)
              and err_replay <= 1e-6 and dropless
              and tied and hidden_off <= tol["first_hidden_relative"]
              and all(g_off[k][DECIDES[k]] <= t_grad[k]
                      for k in ("rest", "experts", "router"))
              and g_off["all"] <= t_grad["all"]
              and update["worst"][0] <= tol["replayed_update_relative"])
    return {"ok": ok,
            "detail": f"{f32['detail']}; first training loss "
            f"{float(first_loss):.6f} (AMP) vs reference {want:.6f} "
            f"(float32) on {built['batch']} sequences: relative difference "
            f"{err:.2e} (tolerance {t_loss or 'none: printed, not decided by'}"
            f"); the forward-only AMP program "
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
            f"left unchanged reads 1); the table word_embedding is read by "
            f"{readers} (tied: {tied}); "
            f"ExpertLoad sums to {rows} in every layer: {dropless}, rows on "
            f"the {cfg.n_held} held experts {held(load)} at the initial "
            f"weights and {held(load_close)} as the window left them; "
            f"tokens whose top-{cfg.top_k} differs from the reference's: "
            f"{differ} of {top.shape[1]}"}
