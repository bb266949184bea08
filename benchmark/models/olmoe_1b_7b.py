"""OLMoE-1B-7B pre-training through the repo's public entry points:
``models.transformer.build_olmoe_pretrain`` (pre-norm blocks, QK-norm, rotary,
flash attention, the dropless ``moe_ffn``, the fused head) + AMP AdamW + the
Executor; weights made on the device by the startup program from the seed."""

import numpy as np

from .. import harness, olmoe_flops
from . import _train


def _olmoe_config(config):
    from paddle_tpu.models import transformer as T
    a = config["assumed"]
    return T.OlmoeConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layer=config["num_hidden_layers"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        d_expert=config["intermediate_size"],
        n_experts=config["num_experts"], top_k=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        rms_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        lb_coef=a["router_aux_loss_coef"], z_coef=a["router_z_loss_coef"])


def _reference_kw(cfg):
    return dict(n_head=cfg.n_head, top_k=cfg.top_k, eps=float(cfg.rms_eps),
                theta=float(cfg.rope_theta))


def make_batch(rng, cfg, batch, seq):
    """Token ids uniform in [1, vocab) (0 is the builders' ignored label);
    the label of a position is the next token, and the last position's a
    further draw (documents are concatenated: every position has one)."""
    ids = rng.randint(1, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
    return {"src_ids": ids[:, :-1].copy(), "lm_label": ids[:, 1:].copy()}


def reference_params(get, cfg):
    """The program's parameters (``get(name)`` -> float32 array) in the
    layout of ``reference/olmoe_1b_7b.py``: the fused [d, 3d] QKV weight
    split into its three."""
    d = cfg.d_model
    blocks = []
    for i in range(cfg.n_layer):
        p = f"dec_{i}"
        qkv = get(f"{p}.attn.qkv.w")
        blocks.append({
            "ln1_w": get(f"{p}.ln1.w"), "wq": qkv[:, :d],
            "wk": qkv[:, d:2 * d], "wv": qkv[:, 2 * d:],
            "q_norm_w": get(f"{p}.attn.q_norm.w"),
            "k_norm_w": get(f"{p}.attn.k_norm.w"),
            "wo": get(f"{p}.attn.out.w"), "ln2_w": get(f"{p}.ln2.w"),
            "router_w": get(f"{p}.moe.router.w"),
            "gate_w": get(f"{p}.moe.gate.w"), "up_w": get(f"{p}.moe.up.w"),
            "down_w": get(f"{p}.moe.down.w")})
    return {"wte": get("word_embedding"), "blocks": blocks,
            "final_norm_w": get("final_norm.w"), "head_w": get("lm_out.w")}


def reference_loss(reference, params, feed, cfg, hidden=None):
    """The reference's loss of ``feed`` and its per-layer top-k choices, one
    sequence at a time (at published widths the chip holds one sequence's
    score matrix and logits, not four).  ``hidden`` [B, T, d]: a program's
    final-norm output; then also, per token, the squared distance from the
    reference's and the reference's own squared size, for
    :func:`hidden_difference` — unlike the loss, which averages rounding away
    over thousands of tokens, that tells the precisions apart."""
    import jax
    import jax.numpy as jnp
    total, tops, off2, size2 = None, [], [], []
    for i in range(feed["src_ids"].shape[0]):
        s = reference.sequence_sums(
            params, jnp.asarray(feed["src_ids"][i:i + 1]),
            jnp.asarray(feed["lm_label"][i:i + 1]), **_reference_kw(cfg))
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
    out = reference.loss_of_sums(total, cfg.lb_coef, cfg.z_coef)
    return (float(out["loss"]), np.concatenate(tops, axis=1),
            (np.concatenate(off2), np.concatenate(size2))
            if hidden is not None else None)


def hidden_difference(per_token, keep=None):
    """``|got - want| / |want|`` (Frobenius) of the final-norm output over
    the tokens of ``keep`` (a mask; all of them without one)."""
    off2, size2 = per_token
    if keep is not None:
        off2, size2 = off2[keep], size2[keep]
    return float(np.sqrt(off2.sum() / size2.sum()))


def tokens_that_differ(top, ref_top):
    """[S] bool from two [L, S, k]: the tokens whose set of experts is not
    the reference's in some layer."""
    return np.any(np.any(np.sort(top, -1) != np.sort(ref_top, -1), axis=-1),
                  axis=0)


def _forward_program(cfg, seq, scope, amp):
    """The same model, forward only, over the parameters of ``scope``; the
    names to fetch: loss, final-norm output, each layer's ExpertLoad and
    TopExperts."""
    import paddle_tpu as pt
    from paddle_tpu.framework import Program, program_guard, scope_guard
    from paddle_tpu.models import transformer as T
    main = Program()
    with scope_guard(scope), program_guard(main, Program()):
        _, parts, loss = T.build_olmoe_pretrain(cfg, seq, is_test=True)
    if amp:
        pt.amp.enable(main)
    tops = [op.outputs["TopExperts"][0] for op in main.global_block().ops
            if op.type == "moe_ffn"]
    return main, [loss.name, parts["hidden"].name], \
        [v.name for v in parts["expert_load"]], tops


def build_train(config, traffic, seed, chips, on_chip):
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt
    from paddle_tpu.framework import (Program, Scope, program_guard,
                                      scope_guard)
    from paddle_tpu.models import transformer as T

    cfg = _olmoe_config(config)
    seq = traffic["seq_len"]
    batch = traffic["batch_per_chip"] * chips
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        _, _, loss = T.build_olmoe_pretrain(cfg, seq)
        pt.amp.decorate(opt.AdamWOptimizer(
            learning_rate=traffic["learning_rate"],
            weight_decay=traffic["weight_decay"])).minimize(loss)
        exe = _train.executor(on_chip)
        exe.run(startup, scope=scope, seed=harness.exe_seed(seed))
    rng = _train.rng_of(seed)
    ring = [make_batch(rng, cfg, batch, seq) for _ in range(traffic["ring"])]
    return {
        "exe": exe, "scope": scope, "cfg": cfg,
        "program": _train.maybe_data_parallel(main, loss, chips),
        "loss": loss.name, "ring": ring, "batch": batch,
        "parameters": main.all_parameters(),
        "flops_per_sample": olmoe_flops.olmoe_train_flops_per_sample(
            cfg.d_model, cfg.n_layer, cfg.n_experts, cfg.top_k, cfg.d_expert,
            cfg.vocab_size, seq),
    }


def check_before_window(config, traffic, built, seed, reference, chips):
    """The float32 forward program (no AMP, matmuls at ``highest``, the same
    weights in the same scope) on a small seeded batch, against the
    reference: the loss, each token's experts and the final-norm output; the
    initial weights go to the host for :func:`check_first_loss`.

    Top-8 of 64 is not continuous: where a token's 8th and 9th router
    probabilities tie to float32's last bits, two sound float32 computations
    choose differently and that token's output is 19-37 % off (1 such token
    of 8192 in 2 of 40 seeds, PERF.md section 6, PR 30) — 2e-3 to 4e-3 over
    the whole batch, which a limit of 1e-3 called not correct.  So the
    output is compared over the tokens whose experts are the reference's,
    and the share of the others is held to a limit of its own."""
    import jax
    import jax.numpy as jnp
    cfg, scope = built["cfg"], built["scope"]
    seq, n = traffic["seq_len"], traffic["check_batch"]
    main, heads, loads, tops = _forward_program(cfg, seq, scope, amp=False)
    feed = make_batch(_train.rng_of(seed, 7), cfg, n, seq)
    # on a TPU a float32 matmul multiplies in bf16 passes unless told
    # otherwise; this program is the float32 one, so it is told
    with jax.default_matmul_precision("highest"):
        got, hidden, *rest = built["exe"].run(
            main, feed=feed, fetch_list=heads + loads + tops, scope=scope)
    want, ref_top, per_token = reference_loss(
        reference, reference_params(
            lambda name: jnp.asarray(scope.find_var(name), jnp.float32), cfg),
        feed, cfg, hidden=hidden)
    # on the host: a step donates the scope's buffers, and a copy kept on
    # the device would count in the system's peak memory
    built["initial"] = {v.name: np.asarray(scope.find_var(v.name), np.float32)
                        for v in built["parameters"]}
    return before_window_verdict(
        config["loss_tolerance"], np.asarray(got), want, per_token,
        np.stack([np.asarray(v).reshape(-1, cfg.top_k)
                  for v in rest[len(loads):]]), ref_top,
        [np.asarray(v) for v in rest[:len(loads)]], n)


def before_window_verdict(tol, got, want, per_token, top, ref_top, load, n):
    """What :func:`check_before_window` decides from what it read: every
    number beside its limit."""
    err = _train.rel_err(got, want)
    differ = tokens_that_differ(top, ref_top)
    share = float(differ.mean())
    hidden_off = hidden_difference(per_token, ~differ)
    rows = top.shape[1] * top.shape[2]
    dropless = all(int(v.sum()) == rows for v in load)
    return {"ok": bool(np.isfinite(err) and err <= tol["relative"]
                       and hidden_off <= tol["hidden_relative"]
                       and share <= tol["top_k_differ_share"] and dropless),
            "detail": f"float32 forward loss {float(got):.6f} vs "
            f"reference {want:.6f} on {n} sequences: relative difference "
            f"{err:.2e} (tolerance {tol['relative']}); tokens whose top-"
            f"{top.shape[2]} differs from the reference's: "
            f"{int(differ.sum())} of {differ.size}, a share of {share:.2e} "
            f"(tolerance {tol['top_k_differ_share']}); final-norm output "
            f"over the others {hidden_off:.2e} from the reference's "
            f"(tolerance {tol['hidden_relative']}; over all tokens "
            f"{hidden_difference(per_token):.2e}); every layer's ExpertLoad "
            f"sums to {rows}: {dropless}"}


def check_first_loss(config, traffic, built, first_loss, first_feed,
                     reference):
    """The loss the timed program itself (AMP AdamW step) fetched for its
    first batch, against the reference's float32 loss over the same batch and
    the initial weights (the model has no dropout, so the two compute the
    same function).  After the window and after the memory reading: the
    training state is let go first, so that the reference and a forward-only
    AMP program over the initial weights fit beside nothing.  That program
    gives what the timed step does not fetch: its final-norm output (held to
    the reference's within bf16's reach), its ExpertLoad (no token dropped:
    it sums to tokens x k) and each token's experts, compared with the
    reference's choice."""
    import jax.numpy as jnp
    from paddle_tpu.framework import Scope
    cfg = built["cfg"]
    old = built["scope"]
    for name in list(old.local_var_names()):
        old.erase(name)
    initial = {k: jnp.asarray(v) for k, v in built.pop("initial").items()}
    scope = Scope()
    scope.set_vars(initial)
    seq = first_feed["src_ids"].shape[1]
    main, heads, loads, tops = _forward_program(cfg, seq, scope, amp=True)
    got, hidden, *rest = built["exe"].run(
        main, feed=first_feed, fetch_list=heads + loads + tops, scope=scope)
    want, ref_top, per_token = reference_loss(
        reference, reference_params(initial.__getitem__, cfg), first_feed,
        cfg, hidden=hidden)
    hidden_off = hidden_difference(per_token)
    load = [np.asarray(v) for v in rest[:len(loads)]]
    top = np.stack([np.asarray(v).reshape(-1, cfg.top_k)
                    for v in rest[len(loads):]])
    rows = top.shape[1] * cfg.top_k
    differ = int(tokens_that_differ(top, ref_top).sum())
    err = _train.rel_err(first_loss, want)
    err_fwd = _train.rel_err(np.asarray(got), first_loss)
    tol = config["loss_tolerance"]
    t_loss = tol["first_training_loss_relative"]
    dropless = all(int(v.sum()) == rows for v in load)
    skew = max(float(v.max()) / float(v.mean()) for v in load)
    return {"ok": bool(np.isfinite(err) and err <= t_loss
                       and err_fwd <= t_loss and dropless
                       and hidden_off <= tol["first_hidden_relative"]),
            "detail": f"first training loss {float(first_loss):.6f} (AMP) vs "
            f"reference {want:.6f} (float32) on {built['batch']} sequences: "
            f"relative difference {err:.2e} (tolerance {t_loss}); the "
            f"forward-only AMP program reads {float(np.asarray(got)):.6f} "
            f"({err_fwd:.2e} from the step's), its final-norm output "
            f"{hidden_off:.2e} from the reference's (tolerance "
            f"{tol['first_hidden_relative']}); ExpertLoad sums to {rows} in "
            f"every layer: {dropless}, max/mean {skew:.3f}; tokens whose "
            f"top-{cfg.top_k} differs from the reference's: {differ} of "
            f"{top.shape[1]}"}
