"""SDAR-30B-A3B-Chat under block-diffusion training, one chip's share of an
expert-parallel-8 stage, through the repo's public entry points:
``models.transformer.build_sdar_pretrain`` (the causal-LM loop over another
objective: a noisy copy beside a clean copy of each document through one
table, ``decoder_block`` over the doubled stream, every layer's attention
under block diffusion's three-part mask inside the flash kernels
(``flash_attention``'s ``block_diffusion`` attribute), per-head QK-norm and
rotary at 32 over 4 heads, softmax top-8 of 128 SiLU-gated experts of which
this chip holds 16, the head over the noisy half and a weighted denoising
loss) + AMP AdamW under ``layers.linear_lr_warmup`` + the Executor; where the
traffic says ``recompute``, under ``RecomputeOptimizer`` at the block
boundaries.

The traffic (``traffic/bd_s8192_b4_r64.json``) fixes the weights (the startup
program draws them from ``weights_seed``); ``--seed`` is the traffic: it draws
the documents' ids, each block's noise level, which tokens become the mask id,
and with them the labels and the weights of the loss.

``correct`` is decided as the Nemotron-3-Nano cell decides it, with the
helpers that cell's adapter imports (``xing4_29b_a4b``'s for the host's
float64 comparisons and the replayed update, ``trinity_mini``'s,
``olmoe_1b_7b``'s, ``ling3_flash_vl``'s), everything after the window and
after the memory reading: nothing of the reference compiles or runs before
the window opens.  The final-norm output is compared over the noisy half
(what the head reads), the experts chosen over all ``2L`` rows of the stream;
the gradient by four kinds of leaf, a block's norm with the sublayer it
feeds: ``attention`` (a layer's projections, its two head norms and the norm
before them: what reaches the loss through the flash backward under the
mask), ``experts`` (the held experts' weights and the norm before them),
``router`` and ``rest`` (the two tables and the final norm)."""

import json

import numpy as np

from .. import harness, sdar_flops
from . import _train
from . import ling3_flash_vl as _ling
from . import olmoe_1b_7b as _olmoe
from . import trinity_mini as _trinity
from . import xing4_29b_a4b as _xing

#: the kinds of leaf a gradient is judged by, and which entry of
#: :func:`gradient_difference`'s tuples is held to the kind's limit: 1 its
#: worst leaf, 3 its median leaf, 4 its smallest leaf.  The
#: mask token's ~4096 rows are one embedding and choose their experts en
#: bloc, so where two of its scores lie within bf16 of each other a whole
#: layer's mask rows go to another expert under AMP than in the float32
#: reference: that layer's router reads 0.1 to 2.3 in sound runs and its
#: experts' leaves up to 0.12, while the other layers read 0.003 to 0.03
#: and 0.007 to 0.012 (configs/sdar_30b_a3b.json, first_gradient_reason)
DECIDES = {"rest": 1, "experts": 3, "router": 4, "attention": 1}
STATISTIC = {1: "worst leaf", 3: "median leaf", 4: "smallest leaf"}
KINDS = tuple(DECIDES)
#: the reference's names of what reaches the loss through the flash kernels
#: alone, and through the held experts alone (the norm before them too: the
#: rows that reach a held expert decide its gradient, as they decide the
#: experts')
ATTENTION_LEAVES = ("wq", "wk", "wv", "wo", "q_norm_w", "k_norm_w", "ln1_w")
EXPERT_LEAVES = ("gate_w", "up_w", "down_w", "ln2_w")
FEEDS = ("clean_ids", "noisy_ids", "lm_label", "loss_weight")


def sdar_config(config):
    from paddle_tpu.models import transformer as T
    a = config["assumed"]
    # what SdarConfig and the builder hold as constants of this family
    assert (config["attention_bias"], config["decoder_sparse_step"],
            config["hidden_act"], config["mlp_only_layers"],
            config["norm_topk_prob"], config["tie_word_embeddings"],
            config["rope_scaling"], config["use_sliding_window"]) == \
        (False, 1, "silu", [], True, False, None, False)
    return T.SdarConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layer=config["num_hidden_layers"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"], d_head=config["head_dim"],
        d_expert=config["moe_intermediate_size"],
        n_experts=a["router_outputs"], top_k=config["num_experts_per_tok"],
        rms_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]),
        block_diffusion=a["block_length"], mask_token_id=a["mask_token_id"],
        n_held=config["num_experts"], expert_offset=a["expert_offset"])


def make_batch(rng, cfg, batch, seq, t_min=1e-3, t_max=1.0):
    """One step's feeds.  ``clean_ids``: documents, ids uniform in [1,
    mask_token_id) (0 is the builders' ignored label and the mask id is no
    document's token).  For each block of ``cfg.block_diffusion`` tokens a
    noise level ``t ~ U[t_min, t_max]``; each token of the block becomes
    the mask id with probability ``t``: ``noisy_ids``.  ``lm_label``: the
    clean id where the noisy one is the mask id, 0 elsewhere;
    ``loss_weight``: ``1 / t`` of the position's block."""
    block = cfg.block_diffusion
    clean = rng.randint(1, cfg.mask_token_id, (batch, seq)).astype(np.int32)
    t = np.repeat(rng.uniform(t_min, t_max, (batch, seq // block)), block,
                  axis=1)
    masked = rng.uniform(size=(batch, seq)) < t
    return {"clean_ids": clean,
            "noisy_ids": np.where(masked, cfg.mask_token_id,
                                  clean).astype(np.int32),
            "lm_label": np.where(masked, clean, 0).astype(np.int32),
            "loss_weight": (1.0 / t).astype(np.float32)}


def reference_kw(cfg, q_block=512):
    return dict(n_head=cfg.n_head, n_kv_head=cfg.n_kv_head,
                d_head=cfg.d_head, top_k=cfg.top_k, eps=float(cfg.rms_eps),
                theta=float(cfg.rope_theta), block=int(cfg.block_diffusion),
                expert_offset=int(cfg.expert_offset), q_block=int(q_block))


def reference_params(get, cfg):
    """The program's parameters (``get(name)`` -> float32 array) in the
    layout of ``reference/sdar_30b_a3b.py``: the fused ``[d, (Hq + 2 Hkv)
    dh]`` projection split into Q, K and V; the rest under the reference's
    names."""
    dq, dkv = cfg.n_head * cfg.d_head, cfg.n_kv_head * cfg.d_head
    blocks = []
    for i in range(cfg.n_layer):
        p = f"dec_{i}"
        w = get(f"{p}.attn.qkv.w")
        blocks.append({
            "ln1_w": get(f"{p}.ln1.w"), "wq": w[:, :dq],
            "wk": w[:, dq:dq + dkv], "wv": w[:, dq + dkv:],
            "q_norm_w": get(f"{p}.attn.q_norm.w"),
            "k_norm_w": get(f"{p}.attn.k_norm.w"),
            "wo": get(f"{p}.attn.out.w"), "ln2_w": get(f"{p}.ln2.w"),
            "router_w": get(f"{p}.moe.router.w"),
            "gate_w": get(f"{p}.moe.gate.w"), "up_w": get(f"{p}.moe.up.w"),
            "down_w": get(f"{p}.moe.down.w")})
    return {"wte": get("word_embedding"), "blocks": blocks,
            "final_norm_w": get("final_norm.w"), "head_w": get("lm_out.w")}


def _feeds(feed, rows=slice(None)):
    import jax.numpy as jnp
    return tuple(jnp.asarray(feed[k][rows]) for k in FEEDS)


def reference_loss(reference, params, feed, cfg, hidden=None, q_block=512):
    """As ``xing4_29b_a4b.reference_loss`` (what ``tools/
    smallthinker_tolerance_probe.py --cell sdar`` reads the control by): the
    reference's loss of ``feed``, its per-layer top-k choices over the
    stream's rows and, with ``hidden`` (a final-norm output over the noisy
    half), per noisy row its squared distance from the reference's and the
    reference's own squared size."""
    import jax
    import jax.numpy as jnp
    total, tops, off2, size2 = None, [], [], []
    for i in range(feed["clean_ids"].shape[0]):
        s = reference.sequence_sums(params, *_feeds(feed, slice(i, i + 1)),
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
    precision, the gradient a tree like ``params``, on the host."""
    import jax
    kw = reference_kw(cfg, q_block)
    want, g_ref = jax.jit(jax.value_and_grad(
        lambda p, *feeds: reference.loss(p, *feeds, **kw)))(
            params, *_feeds(feed))
    return float(want), jax.tree_util.tree_map(
        lambda a: np.asarray(a, np.float32), g_ref)


def reference_value_and_grad(reference, params, feed, cfg, q_block):
    """What the cell reads of the reference, from one program compiled once
    (at the compiler's least effort for the compiled code's speed, as
    ``xing4_29b_a4b.reference_value_and_grad``): ``(loss, top_e [layers, B *
    2L, k], hidden [B, L, d], gradient)`` of the whole batch of ``feed``,
    the gradient a tree like ``params``, on the host."""
    import jax
    kw = reference_kw(cfg, q_block)

    def loss(p, *feeds):
        s = reference.batch_sums(p, *feeds, **kw)
        return reference.loss_of_sums(s)["loss"], (s["top_e"], s["hidden"])

    args = (params,) + _feeds(feed)
    (want, (top_e, hidden)), g_ref = jax.jit(
        jax.value_and_grad(loss, has_aux=True)).lower(*args).compile(
            compiler_options=_xing.REFERENCE_COMPILER_OPTIONS)(*args)
    return float(want), np.asarray(top_e), np.asarray(hidden, np.float32), \
        jax.tree_util.tree_map(np.asarray, g_ref)


def kind_of(name):
    """The kind a leaf of the reference's tree is judged with."""
    if name.endswith(tuple(f"['{k}']" for k in ATTENTION_LEAVES)):
        return "attention"
    if "router_w" in name:
        return "router"
    return "experts" if name.endswith(
        tuple(f"['{k}']" for k in EXPERT_LEAVES)) else "rest"


def gradient_difference(g_ref, grads):
    """``nemotron3_nano_30b_a3b.gradient_difference`` with this model's
    kinds and more of each: ``attention`` (a layer's four projections, its
    two head norms and the norm before them, through the flash backward
    under the block-diffusion mask at 8 query heads a K/V head), ``rest``
    (final norm, embedding, head), ``experts`` (with the norm before them)
    and ``router``.  ``{kind: (leaves together, worst leaf, its name, median
    leaf, smallest leaf), "all": together over every leaf, "leaves":
    {kind: {name: the leaf's}}}``; :data:`DECIDES` says which entry a kind
    is held by."""
    import jax
    sums = {k: [0.0, 0.0] for k in KINDS}
    worst = {k: (0.0, "") for k in KINDS}
    by_leaf = {k: {} for k in KINDS}
    leaves = [(jax.tree_util.keystr(path), (got, ref)) for (path, ref), got in
              zip(jax.tree_util.tree_flatten_with_path(g_ref)[0],
                  jax.tree_util.tree_leaves(grads))]
    for name, d2, r2 in _xing._squares_by_leaf(
            leaves, lambda got, ref: (got.astype(np.float64) - ref, ref)):
        kind = kind_of(name)
        sums[kind][0] += d2
        sums[kind][1] += r2
        e = (d2 / r2) ** 0.5 if r2 > 0 else float(d2 > 0)
        by_leaf[kind][name] = e
        if not e <= worst[kind][0]:             # a NaN is the worst
            worst[kind] = (e, name)
    out = {k: ((d2 / max(r2, 1e-300)) ** 0.5,) + worst[k]
           + (float(np.median(list(by_leaf[k].values()))),
              min(by_leaf[k].values()))
           for k, (d2, r2) in sums.items()}
    out["leaves"] = by_leaf
    out["all"] = (sum(d2 for d2, _ in sums.values())
                  / max(sum(r2 for _, r2 in sums.values()), 1e-300)) ** 0.5
    return out


def _forward_program(cfg, seq, scope, amp):
    """The same model, forward only, over the parameters of ``scope``; the
    names to fetch: loss and final-norm output (the noisy half's), each
    layer's ExpertLoad and TopExperts (all ``2L`` rows')."""
    import paddle_tpu as pt
    from paddle_tpu.framework import Program, program_guard, scope_guard
    from paddle_tpu.models import transformer as T
    main = Program()
    with scope_guard(scope), program_guard(main, Program()):
        _, parts, loss = T.build_sdar_pretrain(cfg, seq)
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

    cfg = sdar_config(config)
    assert traffic["block_length"] == cfg.block_diffusion and \
        traffic["mask_token_id"] == cfg.mask_token_id
    seq = traffic["seq_len"]
    batch = traffic["batch_per_chip"] * chips
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        # the traffic's rule: RecomputeOptimizer at the blocks' boundaries
        # (the doubled stream and the six block outputs) and nothing finer;
        # the plain step where the traffic says recompute false
        checkpoints = [] if traffic.get("recompute") else None
        _, _, loss = T.build_sdar_pretrain(cfg, seq, checkpoints=checkpoints)
        rate = float(traffic["learning_rate"])
        adamw = opt.AdamWOptimizer(
            learning_rate=layers.linear_lr_warmup(
                rate, int(traffic["lr_warmup_steps"]),
                float(traffic["lr_start"]), rate),
            weight_decay=traffic["weight_decay"])
        stepper = adamw
        if checkpoints:
            stepper = opt.RecomputeOptimizer(adamw)
            stepper._set_checkpoints(checkpoints, after_gradient=True)
        pt.amp.decorate(stepper).minimize(loss)
        scale_initial_values(startup, config["assumed"]["initial_scale"])
        exe = _train.executor(on_chip)
        # the weights are the model: the startup program's seed is the
        # traffic's, and --seed draws the feeds alone
        w_seed = int(traffic["weights_seed"])
        exe.run(startup, scope=scope, seed=harness.exe_seed(w_seed))
    rng = _train.rng_of(seed)
    ring = [make_batch(rng, cfg, batch, seq, traffic["noise_t_min"],
                       traffic["noise_t_max"])
            for _ in range(traffic["ring"])]
    return {
        "exe": exe, "scope": scope, "cfg": cfg,
        "program": _train.maybe_data_parallel(main, loss, chips),
        "loss": loss.name, "ring": ring, "batch": batch,
        "parameters": main.all_parameters(),
        "flops_per_sample": sdar_flops.train_flops_per_sample(config, seq),
        # for the checks after the window (``trinity_mini._initial_state``
        # reads "startup" and "seed"): the startup program makes the initial
        # state again from the weights' seed, and a step from zeroed moments
        # leaves (1 - beta1) x its gradient in each parameter's first moment
        "startup": startup, "seed": w_seed, "beta1": adamw._beta1,
        "moment1": {name: v.name for name, v in
                    adamw._accumulators["moment1"].items()},
    }


def scale_initial_values(startup, scales):
    """The configuration's ``assumed.initial_scale`` on the startup program:
    the draw of every parameter whose name ends in a key is that many times
    as wide (the bounds of a uniform draw, the deviation of a normal one),
    so that the startup program makes the stated initial values whenever it
    runs (the checks after the window run it again)."""
    widths = {"uniform_random": ("min", "max"), "gaussian_random": ("std",)}
    for op in startup.global_block().ops:
        factor = [f for key, f in scales.items()
                  if op.outputs["Out"][0].endswith(key)]
        for attr in widths[op.type] if factor else ():
            op.attrs[attr] *= factor[0]


check_before_window = _ling.check_before_window


def decide(tol, r):
    """The cell's decision over its readings ``r`` (floats under the names
    below; :func:`check_first_loss` reads them from the program,
    ``tools/smallthinker_tolerance_probe.py --cell sdar`` from the reference
    computed in bf16 in the program's place): ``(ok, [the limits a reading
    exceeds])``.  A reading that is not a number exceeds its limit."""
    held = [("relative", r["f32_loss"]),
            ("top_k_differ_share", r["f32_share"]),
            ("hidden_relative", r["f32_hidden"]),
            ("first_hidden_relative", r["first_hidden"]),
            ("replayed_update_relative", r["update"])] + [
        (f"first_gradient_{k}_relative", r[f"gradient_{k}"])
        for k in KINDS + ("all", "experts_worst")]
    held += [("first_training_loss_relative", r[k])
             for k in ("first_loss", "first_forward")]
    failed = sorted({name for name, v in held if not v <= tol[name]},
                    key=[name for name, _ in held].index)
    if not r["replay"] <= 1e-6:
        failed.append("replay")
    if not r["dropless"]:
        failed.append("dropless")
    return not failed, failed


def noisy_rows(differ, batch, seq):
    """Of a per-row array over the stream's ``batch * 2 * seq`` rows (a
    sequence's noisy rows, then its clean rows), the noisy rows'."""
    return differ.reshape(batch, 2, seq)[:, 0].reshape(-1)


def check_first_loss(config, traffic, built, first_loss, first_feed,
                     reference):
    """Every comparison of the cell, after the window and after the memory
    reading, each from the initial state the startup program makes again,
    all on the timed document (the first batch of the ring):

    * the routing as the window left it, into the routed-rows counter
      (``trinity_mini._count_loads``);
    * the timed AMP AdamW step itself, once more from the initial state: its
      loss is the one it fetched first in this run, and **its gradient**,
      every parameter's, read from the first moment, against ``jax.grad`` of
      the float32 reference, whose mask is a dense boolean array: the flash
      backward under the block-diffusion mask at 8 query heads a K/V head,
      ``rope_grad`` over the folded copies, ``moe_ffn_grad``'s held path,
      the weighted loss and the AdamW op at the timed sizes;
    * the timed step once more half-way up the warm-up: every parameter's
      change against the reference's AdamW in float64
      (``xing4_29b_a4b._replayed_update``);
    * the float32 forward program (no AMP, matmuls at ``highest``) against
      the reference: loss, each stream row's 8 of 128 experts in every
      layer, the final-norm output over the noisy rows whose experts are
      the reference's;
    * the step's first loss against the reference's, and a forward-only AMP
      program's final-norm output, ExpertLoad and experts a row.

    The reference's loss, experts, final-norm output and gradient come from
    one program compiled once (:func:`reference_value_and_grad`), and the
    host's float64 comparisons run by the chunk on its cores: a run has to
    end well inside the driver's 360 s, and its log line "checks after the
    window" says where these seconds went.  :func:`decide` holds the
    readings to the configuration's limits."""
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

    seq, batch = traffic["seq_len"], built["batch"]
    q_block = traffic.get("reference_q_block", 512)
    fwd_amp = _forward_program(cfg, seq, scope, amp=True)
    load_close = _run_forward(exe, scope, fwd_amp, first_feed, cfg)[2]
    _trinity._count_loads(cfg, load_close)
    mark("AMP forward program at the window's weights")
    replayed, grads = _trinity._replayed_first_step(built, first_feed)
    mark("first step once more")
    update = _xing._replayed_update(built, traffic, first_feed, grads,
                                    reference)
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
        reference, params, first_feed, cfg, q_block)
    del params
    mark("reference loss and gradient")
    differ32 = _olmoe.tokens_that_differ(top32, ref_top)
    r = {"f32_loss": _train.rel_err(got32, want),
         "f32_share": float(differ32.mean()),
         "f32_hidden": _olmoe.hidden_difference(
             _xing.per_token_difference(hidden32, ref_hidden),
             ~noisy_rows(differ32, batch, seq)),
         "first_hidden": _olmoe.hidden_difference(
             _xing.per_token_difference(hidden, ref_hidden))}
    g_off = gradient_difference(
        g_ref, reference_params(grads.__getitem__, cfg))
    harness.log("gradient by leaf: " + json.dumps(g_off.pop("leaves")))
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
        gradient_experts_worst=g_off["experts"][1],
        **{f"gradient_{k}": g_off[k][DECIDES[k]] for k in KINDS})
    ok, failed = decide(tol, r)

    def held(loads_):
        return [int(v[cfg.expert_offset:cfg.expert_offset + cfg.n_held].sum())
                for v in loads_]

    masked = int((first_feed["lm_label"] > 0).sum())
    return {"ok": ok, "readings": r, "detail":
            f"float32 forward loss {got32:.6f} vs reference {want:.6f} on "
            f"{batch} documents of {seq} ({masked} positions masked): "
            f"relative difference {r['f32_loss']:.2e} (tolerance "
            f"{tol['relative']}); stream rows whose top-{cfg.top_k} differs "
            f"from the reference's in some layer: {int(differ32.sum())} of "
            f"{differ32.size}, a share of {r['f32_share']:.2e} (tolerance "
            f"{tol['top_k_differ_share']}); final-norm output over the "
            f"other noisy rows {r['f32_hidden']:.2e} from the reference's "
            f"(tolerance {tol['hidden_relative']}); first training loss "
            f"{float(first_loss):.6f} (AMP) vs reference {want:.6f} "
            f"(float32): relative difference {r['first_loss']:.2e}, the "
            f"forward-only AMP program reads {got:.6f} "
            f"({r['first_forward']:.2e} from the step's) (tolerance "
            f"{tol['first_training_loss_relative']} on each), its final-norm "
            f"output {r['first_hidden']:.2e} from the reference's "
            f"(tolerance {tol['first_hidden_relative']}); the first step "
            f"once more from the startup program's state reads "
            f"{replayed:.6f} ({r['replay']:.2e} from the run's first), its "
            f"gradient against jax.grad of the reference: " + "".join(
                f"{k}: worst leaf {g_off[k][1]:.3e} at {g_off[k][2]}, median "
                f"leaf {g_off[k][3]:.3e}, smallest {g_off[k][4]:.3e}, "
                f"its leaves together {g_off[k][0]:.3e} (tolerance "
                f"{tol[f'first_gradient_{k}_relative']} on the "
                f"{STATISTIC[DECIDES[k]]}), " for k in KINDS) +
            f"the experts' worst leaf besides (tolerance "
            f"{tol['first_gradient_experts_worst_relative']}; a leaf whose "
            f"gradient is lost reads 1), " +
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
            f"the window left them; stream rows whose top-{cfg.top_k} "
            f"differs from the reference's under AMP: {differ} of "
            f"{top.shape[1]}; limits exceeded: {failed or 'none'}"}
