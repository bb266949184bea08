"""ResNet-50 ImageNet training through the repo's public entry points:
``models.resnet.build_resnet_train`` + AMP momentum SGD + the Executor with
the program's default flags, whatever those lower the step to: that is what
users get, and it is why a change of the default path is judged in this
cell."""

import numpy as np

from .. import flops, harness
from . import _train


def make_batch(rng, batch, image, classes):
    return {"image": rng.rand(batch, 3, image, image).astype(np.float32),
            "label": rng.randint(0, classes, (batch, 1)).astype(np.int32)}


def build_train(config, traffic, seed, chips, on_chip):
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt
    from paddle_tpu.framework import (Program, Scope, program_guard,
                                      scope_guard)
    from paddle_tpu.models.resnet import build_resnet_train

    image, classes = config["image_size"], config["num_classes"]
    batch = traffic["batch_per_chip"] * chips
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        _, _, loss, _ = build_resnet_train(
            class_dim=classes, depth=config["depth"],
            image_shape=(3, image, image))
        pt.amp.decorate(opt.MomentumOptimizer(
            learning_rate=traffic["learning_rate"],
            momentum=traffic["momentum"])).minimize(loss)
        exe = _train.executor(on_chip)
        exe.run(startup, scope=scope, seed=harness.exe_seed(seed))
    rng = _train.rng_of(seed)
    ring = [make_batch(rng, batch, image, classes)
            for _ in range(traffic["ring"])]
    return {
        "exe": exe, "scope": scope, "config": config,
        "program": _train.maybe_data_parallel(main, loss, chips),
        "loss": loss.name, "ring": ring, "batch": batch,
        "parameters": main.all_parameters(),
        "flops_per_sample": flops.resnet50_train_flops_per_sample(
            image, classes),
    }


def reference_params(scope, config):
    """Host copies of the scope's parameters in the reference's layout (the
    training step donates the scope's buffers, and a copy kept on the device
    would count in the system's peak memory)."""
    def g(name):
        return np.asarray(scope.find_var(name), np.float32)

    names = [s["name"] for s in flops.resnet50_conv_sites(
        config["image_size"], blocks=tuple(config["stage_blocks"]))]
    return {"convs": {n: g(f"{n}.conv.w") for n in names},
            "bn": {n: (g(f"{n}.bn.scale"), g(f"{n}.bn.offset"))
                   for n in names},
            "fc_w": g("fc_out.w"), "fc_b": g("fc_out.b")}


def check_before_window(config, traffic, built, seed, reference, chips):
    """ResNet-50 has no dropout, so the training program's own first loss is
    a function of the initial weights and the batch.  The weights are copied
    to the host here, before any step; :func:`check_first_loss` compares,
    after the window."""
    built["initial"] = reference_params(built["scope"], config)
    return {"ok": True, "detail": "initial weights kept for the first loss"}


def check_first_loss(config, traffic, built, first_loss, first_feed,
                     reference):
    """The loss the compiled training step (default flags, bf16 AMP) fetched
    for its first batch, against the reference's float32 forward pass with
    batch statistics over the same batch and the initial weights."""
    import jax.numpy as jnp
    n = traffic["check_batch"]
    if n != built["batch"]:
        return {"ok": True, "detail": "first-loss check off: check_batch "
                "differs from the batch the step was compiled for"}
    want = reference.train_loss(
        built.pop("initial"), jnp.asarray(first_feed["image"]),
        jnp.asarray(first_feed["label"])[:, 0],
        eps=float(config["bn_epsilon"]),
        blocks=tuple(config["stage_blocks"]))
    err = _train.rel_err(first_loss, np.asarray(want))
    tol = config["loss_tolerance"]["relative"]
    return {"ok": bool(np.isfinite(err) and err <= tol),
            "detail": f"first training loss {float(first_loss):.6f} vs "
            f"reference {float(np.asarray(want)):.6f} on {n} images: "
            f"relative difference {err:.2e} (tolerance {tol})"}
