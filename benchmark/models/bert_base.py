"""BERT-base masked-LM pre-training through the repo's public entry points:
``models.transformer.build_bert_pretrain`` (fused head, arange positions,
masked gather) + AMP Adam + the Executor; weights made on the device by the
startup program from the seed."""

import numpy as np

from .. import flops, harness
from . import _train
from ._params import transformer_reference_params


def _bert_config(config, dropout=None):
    from paddle_tpu.models import transformer as T
    return T.BertConfig(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layer=config["num_hidden_layers"],
        n_head=config["num_attention_heads"],
        d_inner=config["intermediate_size"],
        max_pos=config["max_position_embeddings"],
        dropout=config["hidden_dropout_prob"] if dropout is None else dropout)


def make_batch(rng, cfg, batch, seq, n_mask):
    pos = np.stack([rng.choice(seq, n_mask, replace=False) + i * seq
                    for i in range(batch)]).astype(np.int32)
    return {"src_ids": rng.randint(1, cfg.vocab_size, (batch, seq)
                                   ).astype(np.int32),
            "mask_pos": pos,
            "lm_label": rng.randint(1, cfg.vocab_size, (batch, n_mask)
                                    ).astype(np.int32)}


def build_train(config, traffic, seed, chips, on_chip):
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt
    from paddle_tpu.framework import (Program, Scope, program_guard,
                                      scope_guard)
    from paddle_tpu.models import transformer as T

    cfg = _bert_config(config)
    seq, n_mask = traffic["seq_len"], traffic["masked_per_seq"]
    batch = traffic["batch_per_chip"] * chips
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        _, _, loss = T.build_bert_pretrain(
            cfg, seq, fused_head=True, arange_pos=True, masked_gather=n_mask)
        pt.amp.decorate(opt.AdamOptimizer(
            learning_rate=traffic["learning_rate"])).minimize(loss)
        exe = _train.executor(on_chip)
        exe.run(startup, scope=scope, seed=harness.exe_seed(seed))
    rng = _train.rng_of(seed)
    ring = [make_batch(rng, cfg, batch, seq, n_mask)
            for _ in range(traffic["ring"])]
    return {
        "exe": exe, "scope": scope, "cfg": cfg,
        "program": _train.maybe_data_parallel(main, loss, chips),
        "loss": loss.name, "ring": ring, "batch": batch,
        "parameters": main.all_parameters(),
        "flops_per_sample": flops.bert_mlm_train_flops_per_sample(
            cfg.d_model, cfg.n_layer, cfg.d_inner, cfg.vocab_size, seq,
            n_mask),
    }


def check_before_window(config, traffic, built, seed, reference, chips):
    """The loss of the same model in test mode (dropout off, the same weights
    in the same scope) on one small seeded batch, against the reference.
    On several chips the check is left to the one-chip cell of the same
    configuration: no second program is compiled on four chips' time."""
    if chips != 1:
        return {"ok": True, "detail": "reference check left to the one-chip "
                "cell of this configuration (no second program on 4 chips)"}
    import jax.numpy as jnp
    from paddle_tpu.framework import Program, program_guard, scope_guard
    from paddle_tpu.models import transformer as T

    cfg = built["cfg"]
    seq, n_mask = traffic["seq_len"], traffic["masked_per_seq"]
    n = traffic["check_batch"]
    test_main = Program()
    with scope_guard(built["scope"]), program_guard(test_main, Program()):
        _, _, loss = T.build_bert_pretrain(
            _bert_config(config, dropout=0.0), seq, is_test=True,
            fused_head=True, arange_pos=True, masked_gather=n_mask)
    feed = make_batch(_train.rng_of(seed, 7), cfg, n, seq, n_mask)
    got, = built["exe"].run(test_main, feed=feed, fetch_list=[loss.name],
                            scope=built["scope"])
    # kept on the host for the check of the timed program's own first loss,
    # which runs after the window
    built["initial"] = transformer_reference_params(
        built["scope"], cfg.n_layer, "mlm_out", on_host=True)
    want = reference.mlm_loss(
        transformer_reference_params(built["scope"], cfg.n_layer, "mlm_out"),
        jnp.asarray(feed["src_ids"]),
        jnp.asarray(feed["mask_pos"]), jnp.asarray(feed["lm_label"]),
        n_head=cfg.n_head, eps=float(config["layer_norm_eps"]))
    err = _train.rel_err(np.asarray(got), np.asarray(want))
    tol = config["loss_tolerance"]["relative"]
    return {"ok": bool(np.isfinite(err) and err <= tol),
            "detail": f"test-mode loss {float(np.asarray(got)):.6f} vs "
            f"reference {float(np.asarray(want)):.6f} on {n} sequences: "
            f"relative difference {err:.2e} (tolerance {tol})"}


def check_first_loss(config, traffic, built, first_loss, first_feed,
                     reference):
    """The loss the timed program itself (AMP, dropout on) fetched for its
    first batch, against the reference's float32 loss without dropout over
    the same batch and the initial weights; computed after the window, from
    weights kept on the host, so that it is in nobody's time or memory.
    Dropout's masks cannot be reproduced outside the program, so the
    tolerance is the size of dropout's effect on the loss of freshly
    initialised weights: this ties the timed program to the reference's model
    and batch, and says nothing finer."""
    import jax.numpy as jnp
    if "initial" not in built:
        return {"ok": True, "detail": "first-loss check left to the one-chip "
                "cell of this configuration"}
    cfg = built["cfg"]
    want = reference.mlm_loss(
        built.pop("initial"), jnp.asarray(first_feed["src_ids"]),
        jnp.asarray(first_feed["mask_pos"]),
        jnp.asarray(first_feed["lm_label"]),
        n_head=cfg.n_head, eps=float(config["layer_norm_eps"]))
    err = _train.rel_err(first_loss, np.asarray(want))
    tol = config["loss_tolerance"]["first_training_loss_relative"]
    return {"ok": bool(np.isfinite(err) and err <= tol),
            "detail": f"first training loss {float(first_loss):.6f} (AMP, "
            f"dropout on) vs reference {float(np.asarray(want)):.6f} "
            f"(float32, no dropout) on {built['batch']} sequences: relative "
            f"difference {err:.2e} (tolerance {tol})"}
