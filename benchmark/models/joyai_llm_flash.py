"""JoyAI-LLM-Flash pre-training, one chip's share, through the repo's public
entry points: ``models.transformer.build_joyai_pretrain`` (latent attention:
low-rank Q and K/V with normed latents, 192-wide scores over 128-wide values,
a rotary slice on adjacent pairs whose key is one head for all; a dense
layer, expert layers with sigmoid routing over 256 experts of which this
chip holds 16 beside a shared expert, and one multi-token-prediction module
over the shared embedding and head) + AMP AdamW + the Executor; weights made
on the device by the startup program from the seed.  ``correct`` is decided
as the Trinity-Mini cell decides it (``models/trinity_mini.py``, whose
helpers this file uses): everything after the window and the memory
reading, the timed step's own first loss and gradient among it."""

import numpy as np

from .. import harness, joyai_flops
from . import _train
from . import olmoe_1b_7b as _olmoe
from . import trinity_mini as _trinity


def joyai_config(config):
    from paddle_tpu.models import transformer as T
    a = config["assumed"]
    assert config["qk_head_dim"] == (config["qk_nope_head_dim"]
                                     + config["qk_rope_head_dim"])
    assert config["n_group"] == config["topk_group"] == 1, \
        "noaux_tc with one group is the path moe_ffn has"
    # what joyai_decoder_layer and latent_attention hold as constants
    assert (config["scoring_func"], config["norm_topk_prob"],
            config["rope_interleave"], config["n_shared_experts"]) == \
        ("sigmoid", True, True, 1)
    return T.JoyaiConfig(
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
        n_held=config["n_routed_experts"], expert_offset=a["expert_offset"])


def make_batch(rng, cfg, batch, seq):
    """Token ids uniform in [1, vocab) (0 is the builders' ignored label),
    ``seq + 2`` a sequence: position ``i`` feeds token ``i``, the main
    label ``i + 1`` and the multi-token-prediction label ``i + 2``
    (documents are concatenated: every position has both)."""
    ids = rng.randint(1, cfg.vocab_size, (batch, seq + 2)).astype(np.int32)
    return {"src_ids": ids[:, :-2].copy(), "lm_label": ids[:, 1:-1].copy(),
            "mtp_label": ids[:, 2:].copy()}


def reference_kw(cfg, q_block=1024):
    return dict(n_head=cfg.n_head, d_nope=cfg.d_nope, d_rope=cfg.d_rope,
                d_v=cfg.d_v, top_k=cfg.top_k, eps=float(cfg.rms_eps),
                theta=float(cfg.rope_theta),
                route_scale=float(cfg.route_scale),
                expert_offset=int(cfg.expert_offset), q_block=int(q_block))


def _reference_block(get, cfg, p, dense):
    a = get(f"{p}.attn.a.w")
    blk = {"w_qa": a[:, :cfg.q_lora_rank], "w_kva": a[:, cfg.q_lora_rank:],
           "q_norm_w": get(f"{p}.attn.q_norm.w"),
           "kv_norm_w": get(f"{p}.attn.kv_norm.w"),
           "w_qb": get(f"{p}.attn.q_b.w"), "w_kvb": get(f"{p}.attn.kv_b.w"),
           "wo": get(f"{p}.attn.out.w"),
           "ln1_w": get(f"{p}.ln1.w"), "ln2_w": get(f"{p}.ln2.w")}
    if dense:
        gu, f = get(f"{p}.ffn.gate_up.w"), cfg.d_inner
        blk.update(ffn_gate=gu[:, :f], ffn_up=gu[:, f:],
                   ffn_down=get(f"{p}.ffn.down.w"))
    else:
        gu, f = get(f"{p}.shared.gate_up.w"), cfg.d_expert
        blk.update(shared_gate=gu[:, :f], shared_up=gu[:, f:],
                   shared_down=get(f"{p}.shared.down.w"),
                   router_w=get(f"{p}.moe.router.w"),
                   select_bias=get(f"{p}.moe.select_bias"),
                   gate_w=get(f"{p}.moe.gate.w"), up_w=get(f"{p}.moe.up.w"),
                   down_w=get(f"{p}.moe.down.w"))
    return blk


def reference_params(get, cfg):
    """The program's parameters (``get(name)`` -> float32 array) in the
    layout of ``reference/joyai_llm_flash.py``: the fused [d, r_q + r_kv +
    d_rope] down-projection split into ``w_qa`` and ``w_kva``, the fused
    gate-up weights into their two; the embedding and the head once, as the
    program holds them."""
    out = {"wte": get("word_embedding"),
           "blocks": [_reference_block(get, cfg, f"dec_{i}",
                                       i < cfg.n_dense_layer)
                      for i in range(cfg.n_layer)],
           "final_norm_w": get("final_norm.w"), "head_w": get("lm_out.w")}
    if cfg.n_mtp:
        out["mtp"] = {"enorm_w": get("mtp_0.enorm.w"),
                      "hnorm_w": get("mtp_0.hnorm.w"),
                      "eh_w": get("mtp_0.eh_proj.w"),
                      "block": _reference_block(get, cfg, "mtp_0", False),
                      "norm_w": get("mtp_0.shared_head_norm.w")}
    return out


def _per_token(got, want):
    import jax.numpy as jnp
    got = jnp.asarray(got, jnp.float32)
    want = want.astype(jnp.float32)
    return (np.asarray(jnp.sum(jnp.square(got - want), axis=-1),
                       np.float64).ravel(),
            np.asarray(jnp.sum(jnp.square(want), axis=-1),
                       np.float64).ravel())


def reference_loss(reference, params, feed, cfg, mtp_weight, hidden=None,
                   q_block=1024):
    """The reference's ``{"loss", "main", "mtp"}`` of ``feed`` and its
    per-layer top-k choices, one sequence at a time; with ``hidden`` (a
    program's final-norm and MTP-norm outputs, each [B, T, d]) also, for
    each, per token its squared distance from the reference's and the
    reference's own squared size (``olmoe_1b_7b.hidden_difference``)."""
    import jax
    import jax.numpy as jnp
    total, tops = None, []
    per = [([], []) for _ in (hidden or ())]
    for i in range(feed["src_ids"].shape[0]):
        s = reference.sequence_sums(
            params, *(jnp.asarray(feed[k][i:i + 1])
                      for k in ("src_ids", "lm_label", "mtp_label")),
            **reference_kw(cfg, q_block))
        tops.append(np.asarray(s.pop("top_e")))
        wants = [s.pop("hidden"), s.pop("mtp_hidden", None)]
        for (off2, size2), got, want in zip(per, hidden or (), wants):
            o, z = _per_token(got[i:i + 1], want)
            off2.append(o)
            size2.append(z)
        total = s if total is None else \
            jax.tree_util.tree_map(jnp.add, total, s)
    out = {k: float(v) for k, v in
           reference.loss_of_sums(total, mtp_weight).items()}
    return out, np.concatenate(tops, axis=1), [
        (np.concatenate(o), np.concatenate(z)) for o, z in per]


def _forward_program(cfg, seq, scope, amp, mtp_weight):
    """The same model, forward only, over the parameters of ``scope``; the
    names to fetch: loss, its two terms, the final-norm and MTP-norm
    outputs, each expert layer's ExpertLoad and TopExperts (the MTP
    module's last)."""
    import paddle_tpu as pt
    from paddle_tpu.framework import Program, program_guard, scope_guard
    from paddle_tpu.models import transformer as T
    main = Program()
    with scope_guard(scope), program_guard(main, Program()):
        _, parts, loss = T.build_joyai_pretrain(cfg, seq, mtp_weight)
    if amp:
        pt.amp.enable(main)
    tops = [op.outputs["TopExperts"][0] for op in main.global_block().ops
            if op.type == "moe_ffn"]
    heads = [loss.name] + [parts[k].name for k in (
        "main_loss", "mtp_loss", "hidden", "mtp_hidden")]
    return main, heads, [v.name for v in parts["expert_load"]], tops


def build_train(config, traffic, seed, chips, on_chip):
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt
    from paddle_tpu.framework import (Program, Scope, program_guard,
                                      scope_guard)
    from paddle_tpu.models import transformer as T

    cfg = joyai_config(config)
    seq = traffic["seq_len"]
    batch = traffic["batch_per_chip"] * chips
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        # ISSUE 34: no recomputation if the step fits; it does not (PERF.md
        # section 6), so checkpoints at the six block outputs and nothing
        # finer, as the traffic file says
        assert traffic["recompute"] is True
        checkpoints = []
        _, _, loss = T.build_joyai_pretrain(
            cfg, seq, traffic["mtp_loss_weight"], checkpoints=checkpoints)
        adamw = opt.AdamWOptimizer(learning_rate=traffic["learning_rate"],
                                   weight_decay=traffic["weight_decay"])
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
        "flops_per_sample": joyai_flops.train_flops_per_sample(config, seq),
        # for the checks after the window (models/trinity_mini.py): the
        # startup program makes the initial state again from the seed, and a
        # step from zeroed moments leaves (1 - beta1) x its gradient in each
        # parameter's first moment
        "startup": startup, "seed": seed, "beta1": adamw._beta1,
        "adamw": dict(lr=traffic["learning_rate"],
                      weight_decay=traffic["weight_decay"],
                      beta1=adamw._beta1, beta2=adamw._beta2,
                      epsilon=adamw._epsilon),
        "moment1": {name: v.name for name, v in
                    adamw._accumulators["moment1"].items()},
    }


def check_before_window(config, traffic, built, seed, reference, chips):
    """Nothing before the window, as in Trinity's cell: a float32 forward
    program beside the step's state would put a heap peak of its own into
    ``peak_hbm_gb``, so every comparison runs after the window and the
    memory reading (:func:`check_first_loss`), from the initial state the
    startup program makes again from the seed."""
    return {"ok": True,
            "detail": "no check before the window: the float32 forward "
            "program, the step's own first loss and its first gradient are "
            "compared with the reference after the window and after the "
            "memory reading, from the initial state the startup program "
            "makes again from the seed"}


def reference_gradient(reference, params, feed, cfg, mtp_weight, q_block):
    """``(loss, gradient)`` of the float32 reference on ``feed``, the
    gradient a tree like ``params`` on the host."""
    import jax
    import jax.numpy as jnp
    kw = reference_kw(cfg, q_block)
    want, g_ref = jax.jit(jax.value_and_grad(
        lambda p, ids, l1, l2: reference.loss(p, ids, l1, l2, mtp_weight,
                                              **kw)))(
        params, *(jnp.asarray(feed[k])
                  for k in ("src_ids", "lm_label", "mtp_label")))
    return float(want), jax.tree_util.tree_map(np.asarray, g_ref)


def _run_forward(exe, scope, fwd, feed, cfg):
    """One forward program's fetches, sorted: ``(losses [3], hidden [2],
    loads, tops [L, S, k])``."""
    main, heads, loads, tops = fwd
    got = exe.run(main, feed=feed, fetch_list=heads + loads + tops,
                  scope=scope)
    rest = got[len(heads):]
    return ([float(np.asarray(v)) for v in got[:3]], got[3:5],
            [np.asarray(v) for v in rest[:len(loads)]],
            np.stack([np.asarray(v).reshape(-1, cfg.top_k)
                      for v in rest[len(loads):]]))


KINDS = ("rest", "experts", "router")


def decide(tol, r):
    """The cell's decision over its readings ``r`` (floats under the names
    below; :func:`check_first_loss` reads them from the program,
    ``tools/joyai_tolerance_probe.py`` from the reference computed in bf16
    in the program's place): ``(ok, [the limits a reading exceeds])``.  A
    reading that is not a number exceeds its limit."""
    held = [
        ("relative", np.max([r["f32_loss"], r["f32_main"], r["f32_mtp"]])),
        ("top_k_differ_share", r["f32_share"]),
        ("hidden_relative", np.max([r["f32_hidden"], r["f32_mtp_hidden"]])),
        ("first_training_loss_relative",
         np.max([r["first_loss"], r["first_terms"], r["first_forward"]])),
        ("first_hidden_relative",
         np.max([r["first_hidden"], r["first_mtp_hidden"]])),
        ("first_update_of_gradient_relative", r["update_of_gradient"]),
    ] + [(f"first_{what}_{k}_relative", r[f"{what}_{k}"])
         for what in ("gradient", "update") for k in KINDS + ("all",)]
    failed = [name for name, v in held if not v <= tol[name]]
    if not r["replay"] <= 1e-6:
        failed.append("replay")
    if not r["dropless"]:
        failed.append("dropless")
    return not failed, failed


def difference(ref, got):
    """``trinity_mini.gradient_difference`` of two trees of float32 leaves
    at one float32 pass over each (680 M numbers a tree here, three
    comparisons a run): every leaf is handed over as two numbers that keep
    its ``|got - ref|`` and ``|ref|``, so the sums by leaf, by kind and
    over all leaves are the leaves' own."""
    import jax
    leaves, tree = jax.tree_util.tree_flatten(ref)
    refs, gots = [], []
    for r, g in zip(leaves, jax.tree_util.tree_leaves(got)):
        r = np.asarray(r, np.float32).ravel()
        d = np.asarray(g, np.float32).ravel() - r
        size = float(np.dot(r, r)) ** 0.5
        refs.append(np.array([size, 0.0]))
        gots.append(np.array([size, float(np.dot(d, d)) ** 0.5]))
    return _trinity.gradient_difference(tree.unflatten(refs),
                                        tree.unflatten(gots))


def update_difference(reference, theta0, delta, g_ref, g_own, adamw):
    """The first step's parameter change ``delta`` (theta_1 - theta_0,
    every leaf, the reference's layout) against the reference's AdamW step
    (``reference.adamw_first_step``) from ``jax.grad`` of the reference, as
    the gradients are compared -- a state left unchanged reads 1 in every
    leaf -- and against the same step from the gradient the program itself
    read (worst leaf): the update alone, lr, decay and what ``ParamOut``
    wrote.  ``(the first as gradient_difference returns it, the second's
    worst leaf)``."""
    import jax

    def step(g):
        return jax.tree_util.tree_map(
            lambda t, g_: reference.adamw_first_step(t, g_, **adamw),
            theta0, g)

    off = difference(step(g_ref), delta)
    own = difference(step(g_own), delta)
    return off, float(np.max([own[k][0] for k in KINDS]))


def check_first_loss(config, traffic, built, first_loss, first_feed,
                     reference):
    """Every comparison of the cell, after the window and after the memory
    reading, each from the initial state the startup program makes again
    (``models/trinity_mini.py`` has the helpers and the reasons):

    * the routing as the window left it, each expert layer's and the MTP
      module's ExpertLoad into the program's routed-rows counter;
    * the timed AMP AdamW step itself, once more from the initial state: its
      loss is the one it fetched first in this run; **its gradient**, every
      parameter's, read from the first moment, against ``jax.grad`` of the
      float32 reference on the same 8192 tokens (the two-width flash
      backward, the rotary slice's and the shared key's gradients, the
      embedding's and the head's as the sums of their two uses,
      ``moe_ffn_grad``'s held path); and **what it wrote**, every
      parameter's change, against the reference's AdamW step
      (:func:`update_difference`: a state left unchanged, a rate or a decay
      that is not the traffic file's, a master weight written back in bf16);
    * the float32 forward program (no AMP, matmuls at ``highest``) on a
      seeded batch of its own against the reference: the loss and both its
      terms, each token's 8 of 256 experts in every expert layer and the
      MTP module, the final-norm and the MTP-norm outputs over the tokens
      whose experts are the reference's;
    * the step's first loss against the reference's, and a forward-only AMP
      program's two outputs, ExpertLoad and experts a token.

    :func:`decide` holds the readings to the configuration's limits."""
    import jax
    import jax.numpy as jnp
    cfg, scope, exe = built["cfg"], built["scope"], built["exe"]
    tol = config["loss_tolerance"]
    seq, n = traffic["seq_len"], traffic["check_batch"]
    lam = traffic["mtp_loss_weight"]
    q_block = traffic.get("reference_q_block", 1024)
    fwd_amp = _forward_program(cfg, seq, scope, True, lam)
    load_close = _trinity._routing_at_close(
        built, (fwd_amp[0], None, fwd_amp[2], None), first_feed)
    replayed, grads = _trinity._replayed_first_step(built, first_feed)
    delta = {v.name: np.array(scope.find_var(v.name), np.float32)
             for v in built["parameters"]}

    _trinity._initial_state(built)
    theta0 = {name: np.asarray(scope.find_var(name), np.float32)
              for name in delta}
    for name, after in delta.items():
        after -= theta0[name]
    _trinity._erase(scope, keep={v.name for v in built["parameters"]})

    def initial(name):
        return jnp.asarray(scope.find_var(name), jnp.float32)

    # the float32 forward program on its own batch
    fwd32 = _forward_program(cfg, seq, scope, False, lam)
    feed = make_batch(_train.rng_of(built["seed"], 7), cfg, n, seq)
    with jax.default_matmul_precision("highest"):
        got, hidden, load32, top32 = _run_forward(exe, scope, fwd32, feed,
                                                  cfg)
    want, ref_top, per_token = reference_loss(
        reference, reference_params(initial, cfg), feed, cfg, lam,
        hidden=hidden, q_block=q_block)
    differ32 = _olmoe.tokens_that_differ(top32, ref_top)
    r = {"f32_loss": _train.rel_err(got[0], want["loss"]),
         "f32_main": _train.rel_err(got[1], want["main"]),
         "f32_mtp": _train.rel_err(got[2], want["mtp"]),
         "f32_share": float(differ32.mean()),
         "f32_hidden": _olmoe.hidden_difference(per_token[0], ~differ32),
         "f32_mtp_hidden": _olmoe.hidden_difference(per_token[1], ~differ32)}
    del hidden, per_token

    # the forward-only AMP program on the step's first batch
    got_amp, hidden, load, top = _run_forward(exe, scope, fwd_amp,
                                              first_feed, cfg)
    params = reference_params(initial, cfg)
    want_amp, ref_top, per_token = reference_loss(
        reference, params, first_feed, cfg, lam, hidden=hidden,
        q_block=q_block)
    r["first_hidden"], r["first_mtp_hidden"] = (
        _olmoe.hidden_difference(p) for p in per_token)
    del hidden, per_token

    # the step's gradient and what it wrote: the fused weights go, the
    # reference's stay
    _trinity._erase(scope)
    want_g, g_ref = reference_gradient(reference, params, first_feed, cfg,
                                       lam, q_block)

    def in_layout(arrays):
        return reference_params(lambda name: arrays.get(
            name, np.zeros(cfg.n_experts, np.float32)), cfg)

    g_own = in_layout(grads)
    g_off = difference(g_ref, g_own)
    u_off, u_own = update_difference(reference, in_layout(theta0),
                                     in_layout(delta), g_ref, g_own,
                                     built["adamw"])
    del g_ref, g_own, grads, delta, theta0, params

    rows = top.shape[1] * cfg.top_k
    differ = int(_olmoe.tokens_that_differ(top, ref_top).sum())
    r.update(
        first_loss=_train.rel_err(first_loss, want_amp["loss"]),
        first_terms=max(_train.rel_err(got_amp[1], want_amp["main"]),
                        _train.rel_err(got_amp[2], want_amp["mtp"])),
        first_forward=_train.rel_err(got_amp[0], first_loss),
        replay=_train.rel_err(replayed, first_loss),
        dropless=all(int(v.sum()) == rows
                     for v in load + load_close + load32),
        update_of_gradient=u_own,
        gradient_all=g_off["all"], update_all=u_off["all"],
        **{f"gradient_{k}": g_off[k][0] for k in KINDS},
        **{f"update_{k}": u_off[k][0] for k in KINDS})
    ok, failed = decide(tol, r)

    def held(loads_):
        return [int(v[cfg.expert_offset:cfg.expert_offset + cfg.n_held].sum())
                for v in loads_]

    def leaves(off, what):
        return "".join(
            f"worst {k} leaf {off[k][0]:.3e} at {off[k][1]} (tolerance "
            f"{tol[f'first_{what}_{k}_relative']}), " for k in KINDS) + \
            f"all leaves together {off['all']:.3e} (tolerance " \
            f"{tol[f'first_{what}_all_relative']})"

    return {"ok": ok, "readings": r, "detail":
            f"float32 forward loss {got[0]:.6f} (main {got[1]:.6f}, MTP "
            f"{got[2]:.6f}) vs reference {want['loss']:.6f} "
            f"({want['main']:.6f}, {want['mtp']:.6f}) on {n} sequences: "
            f"relative differences {r['f32_loss']:.2e}, {r['f32_main']:.2e}, "
            f"{r['f32_mtp']:.2e} (tolerance {tol['relative']}); tokens whose "
            f"top-{cfg.top_k} differs from the reference's in some layer: "
            f"{int(differ32.sum())} of {differ32.size}, a share of "
            f"{r['f32_share']:.2e} (tolerance {tol['top_k_differ_share']}); "
            f"over the others the final-norm output {r['f32_hidden']:.2e} "
            f"and the MTP-norm output {r['f32_mtp_hidden']:.2e} from the "
            f"reference's (tolerance {tol['hidden_relative']}); first "
            f"training loss {float(first_loss):.6f} (AMP) vs reference "
            f"{want_amp['loss']:.6f} (float32) on {built['batch']} "
            f"sequences: relative difference {r['first_loss']:.2e}, the "
            f"forward-only AMP program's two terms {got_amp[1]:.6f} and "
            f"{got_amp[2]:.6f} vs {want_amp['main']:.6f} and "
            f"{want_amp['mtp']:.6f}: worst {r['first_terms']:.2e}, its loss "
            f"{r['first_forward']:.2e} from the step's (tolerance "
            f"{tol['first_training_loss_relative']}); its final-norm output "
            f"{r['first_hidden']:.2e} and MTP-norm output "
            f"{r['first_mtp_hidden']:.2e} from the reference's (tolerance "
            f"{tol['first_hidden_relative']}); the first step once more from "
            f"the startup program's state reads {replayed:.6f} "
            f"({r['replay']:.2e} from the run's first), its gradient against "
            f"jax.grad of the reference (loss {want_g:.6f}): "
            f"{leaves(g_off, 'gradient')}; every parameter's change in that "
            f"step against the reference's AdamW step from that gradient (a "
            f"state left unchanged reads 1): {leaves(u_off, 'update')}; "
            f"against the reference's AdamW step from the gradient the "
            f"program read, worst leaf {u_own:.3e} (tolerance "
            f"{tol['first_update_of_gradient_relative']}); ExpertLoad sums "
            f"to {rows} in every layer: {r['dropless']}, rows on the "
            f"{cfg.n_held} held experts {held(load)} at the initial weights "
            f"and {held(load_close)} as the window left them (the MTP "
            f"module's last); tokens whose top-{cfg.top_k} differs from the "
            f"reference's under AMP: {differ} of {top.shape[1]}; limits "
            f"exceeded: {failed or 'none'}"}
