"""Compiled-kernel sweep: every Pallas kernel in ``paddle_tpu/pallas``
compiled by Mosaic (never interpreted) on the real chip and compared with
its in-tree reference, at the shapes the models actually reach.

Run (on a chip, one process):
    PADDLE_TPU_TEST_HW=1 python -m pytest -m tpu_hw tests/test_tpu_kernels.py -q
Skipped automatically on the CPU-mesh test config; the two table tests at
the bottom run on CPU.

Tolerances are on ``max|got - want| / max|want|`` (scale-free: gradient
magnitudes grow with T): 3e-2 for bf16 inputs (8 mantissa bits, f32
accumulation in-kernel), 1e-2 for f32 inputs (the MXU's default-precision
passes inside the kernel against a highest-precision reference).
"""

import functools
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

tpu_hw = pytest.mark.tpu_hw

TOL = {jnp.bfloat16: 3e-2, jnp.float32: 1e-2}


def _record(kernel, **metrics):
    path = os.environ.get("PADDLE_TPU_NUMERICS_OUT")
    if path:
        with open(path, "a") as f:
            f.write(json.dumps({"kernel": kernel, **metrics}) + "\n")


def _rel_err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-6))


def _rand(shape, seed, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape)
                       .astype(np.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# flash attention: forward + both backward implementations
# ---------------------------------------------------------------------------

def _flash_case(t, d, heads, dtype, causal, bwd_impl, bias=False):
    from paddle_tpu.pallas import flash_attention, mha_reference

    shape = (1, heads, t, d)
    q, k, v, w = (_rand(shape, s, dtype, 0.5) for s in range(4))
    b = _rand((1, 1, t, t), 9, jnp.float32, 0.5) if bias else None

    def loss(fn, **kw):
        def go(q, k, v):
            o = fn(q, k, v, bias=b, causal=causal, **kw)
            return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32))
        return go

    got = (flash_attention(q, k, v, bias=b, causal=causal),) + jax.grad(
        loss(flash_attention, bwd_impl=bwd_impl), argnums=(0, 1, 2))(q, k, v)
    with jax.default_matmul_precision("highest"):
        f32 = [a.astype(jnp.float32) for a in (q, k, v)]
        want = (mha_reference(*f32, bias=b, causal=causal),) + jax.grad(
            loss(mha_reference), argnums=(0, 1, 2))(*f32)
    errs = {n: _rel_err(g, r)
            for n, g, r in zip(("o", "dq", "dk", "dv"), got, want)}
    _record("flash_attention", t=t, d=d, heads=heads, causal=causal,
            dtype=jnp.dtype(dtype).name, bwd_impl=bwd_impl, bias=bias,
            **errs)
    assert max(errs.values()) < TOL[dtype], errs


@tpu_hw
@pytest.mark.parametrize("bwd_impl", ["fused", "split"])
@pytest.mark.parametrize("t,causal,heads", [
    (2048, True, 4),      # _FWD_DEFAULTS (1024,1024) / _BWD (1024,512)
    (4096, False, 2),     # (512,2048) / (1024,1024)
    (8192, False, 1),     # (512,2048) / (1024,512)
    (16384, False, 1),    # (512,2048) / (1024,1024)
])
def test_flash_table_entries_d64(t, causal, heads, bwd_impl):
    """Every entry of the per-length block tables, bf16 as under AMP."""
    _flash_case(t, 64, heads, jnp.bfloat16, causal, bwd_impl)


@tpu_hw
@pytest.mark.parametrize("bwd_impl", ["fused", "split"])
def test_flash_d128_baseline_blocks(bwd_impl):
    """d > 64 skips the tables: the (512, 1024) baseline blocks."""
    _flash_case(2048, 128, 2, jnp.bfloat16, True, bwd_impl)


@tpu_hw
@pytest.mark.parametrize("causal", [False, True])
def test_flash_padded_length(causal):
    """T = 1000 divides no block: the padding masks run."""
    _flash_case(1000, 64, 2, jnp.float32, causal, "fused")
    _flash_case(1000, 64, 2, jnp.float32, causal, "split")


@tpu_hw
def test_flash_with_bias():
    """A [1, 1, T, T] bias rides the forward kernel (the backward with a
    bias is the blockwise-jax path by design)."""
    _flash_case(1024, 64, 2, jnp.float32, False, "fused", bias=True)


#: the three flash cells' shapes, fewer heads (the dense oracle holds
#: [heads, T, T] float32 scores): name -> (T, d_qk, d_v, heads, KV heads,
#: window)
CELL_SHAPES = {
    "joyai_192_over_128": (8192, 192, 128, 2, 2, None),
    "trinity_full": (8192, 128, 128, 4, 1, None),
    "trinity_window_2048": (8192, 128, 128, 4, 1, 2048),
    "olmoe_4096": (4096, 128, 128, 4, 4, None),
    "padded_length_192_over_128": (3000, 192, 128, 2, 1, None),
}


@tpu_hw
@pytest.mark.parametrize("bwd_impl", ["fused", "split", None])
@pytest.mark.parametrize("case", sorted(CELL_SHAPES))
def test_flash_backwards_at_the_cells_shapes(case, bwd_impl):
    """The backward that ships at each flash cell's shape (``None``: the
    tables' own choice) and the two it is chosen from, bf16 as in the cells,
    against ``mha_reference`` in float32; the fused kernel also against the
    split kernels on the same inputs (the same products in the same order:
    within bf16's rounding of the results)."""
    from paddle_tpu.pallas import flash_attention, mha_reference

    t, d_qk, d_v, heads, kv_heads, window = CELL_SHAPES[case]
    q, w = _rand((1, heads, t, d_qk), 0, jnp.bfloat16, 0.5), \
        _rand((1, heads, t, d_v), 3, jnp.bfloat16, 0.5)
    k, v = _rand((1, kv_heads, t, d_qk), 1, jnp.bfloat16, 0.5), \
        _rand((1, kv_heads, t, d_v), 2, jnp.bfloat16, 0.5)

    def grads(fn, *args, **kw):
        return jax.grad(lambda q, k, v: jnp.sum(
            fn(q, k, v, causal=True, window=window, **kw).astype(jnp.float32)
            * w.astype(jnp.float32)), argnums=(0, 1, 2))(*args)

    got = grads(flash_attention, q, k, v, bwd_impl=bwd_impl)
    with jax.default_matmul_precision("highest"):
        want = grads(mha_reference, *(a.astype(jnp.float32)
                                      for a in (q, k, v)))
    errs = {n: _rel_err(g, r) for n, g, r in zip(("dq", "dk", "dv"), got,
                                                 want)}
    if bwd_impl == "fused":
        split = grads(flash_attention, q, k, v, bwd_impl="split")
        errs.update({n + "_vs_split": _rel_err(g, r) for n, g, r in zip(
            ("dq", "dk", "dv"), got, split)})
    _record("flash_attention_cells", case=case, bwd_impl=bwd_impl, **errs)
    assert max(errs.values()) < TOL[jnp.bfloat16], errs


# ---------------------------------------------------------------------------
# RN50, batch 256, default flags: XLA's own convolution fusions, no kernel
# ---------------------------------------------------------------------------

@tpu_hw
def test_rn50_step_batch256_default_flags(tmp_path):
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt
    from paddle_tpu.framework import (Program, Scope, program_guard,
                                      scope_guard)
    from paddle_tpu.models.resnet import build_resnet_train

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from chip_smoke import lowered_kernel_names

    assert pt.get_flags("FLAGS_graph_fusion")["FLAGS_graph_fusion"]
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        _, _, loss, _ = build_resnet_train(class_dim=1000, depth=50)
        pt.amp.decorate(opt.MomentumOptimizer(
            learning_rate=0.1, momentum=0.9)).minimize(loss)
        exe = pt.Executor(pt.TPUPlace(0))
        exe.run(pt.default_startup_program(), scope=scope, seed=3)
        rng = np.random.RandomState(0)
        feed = {"image": rng.rand(256, 3, 224, 224).astype(np.float32),
                "label": rng.randint(0, 1000, (256, 1)).astype(np.int32)}
        jax.config.update("jax_dump_ir_to", str(tmp_path))
        try:
            l0, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope)
        finally:
            jax.config.update("jax_dump_ir_to", None)
        l1, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope)
    kernels = lowered_kernel_names(str(tmp_path))
    _record("rn50_step_b256", losses=[float(l0), float(l1)],
            mosaic_kernels=len(kernels))
    assert np.isfinite(l0) and np.isfinite(l1), (l0, l1)
    # the step is conv2d / batch_norm / relu and their grad ops, which XLA
    # fuses itself: no Mosaic kernel in any dumped module
    assert kernels == [], kernels


@tpu_hw
@pytest.mark.parametrize("case", sorted(CELL_SHAPES))
def test_flash_forwards_lse_at_the_cells_shapes(case):
    """The compiled forward at each flash cell's shape hands on ``lse`` as
    ``[b, h, Tq]`` float32 — out of the kernel as ``[bh, 1, Tq]`` rows at
    every table row (a padded length too: its blocks fill lanes) — equal to
    the log-sum-exp of the dense scores over the same bf16 inputs, and the
    output to ``mha_reference``'s."""
    from paddle_tpu.pallas import mha_reference
    from paddle_tpu.pallas.flash_attention import (flash_attention_fwd,
                                                   flash_lse_layout)

    t, d_qk, d_v, heads, kv_heads, window = CELL_SHAPES[case]
    q = _rand((1, heads, t, d_qk), 0, jnp.bfloat16, 0.5)
    k = _rand((1, kv_heads, t, d_qk), 1, jnp.bfloat16, 0.5)
    v = _rand((1, kv_heads, t, d_v), 2, jnp.bfloat16, 0.5)
    assert flash_lse_layout(q, k, v, causal=True, window=window) == "row"
    out, lse = jax.jit(functools.partial(
        flash_attention_fwd, causal=True, window=window))(q, k, v)
    assert lse.shape == (1, heads, t) and lse.dtype == jnp.float32
    f32 = [a.astype(jnp.float32) for a in (q, k, v)]
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("bhqd,bhkd->bhqk", f32[0], jnp.repeat(
            f32[1], heads // kv_heads, axis=1)) * d_qk ** -0.5
        i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
        mask = (i >= j) if window is None else (i >= j) & (i - j < window)
        want_lse = jax.nn.logsumexp(jnp.where(mask, s, -jnp.inf), axis=-1)
        want = mha_reference(*f32, causal=True, window=window)
    errs = {"o": _rel_err(out, want), "lse": _rel_err(lse, want_lse)}
    _record("flash_attention_fwd_cells", case=case, **errs)
    assert errs["o"] < TOL[jnp.bfloat16] and errs["lse"] < 1e-2, errs


# ---------------------------------------------------------------------------
# a toy OLMoE step: the flash forward kernel once a layer, not twice
# ---------------------------------------------------------------------------

@tpu_hw
def test_olmoe_toy_step_holds_one_flash_fwd_a_layer(tmp_path):
    """Two OLMoE blocks at the model's head width (16 x 128) over 1024
    positions, AMP AdamW: ``flash_attention_grad`` takes the forward's Out
    and Lse, so the lowered step holds the forward kernel once a layer (the
    generic vjp lowered it a second time inside the grad op) beside each
    layer's backward kernels."""
    import collections

    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt
    from paddle_tpu.framework import (Program, Scope, program_guard,
                                      scope_guard)
    from paddle_tpu.models import transformer as T

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmark.models.olmoe_1b_7b import make_batch
    from chip_smoke import lowered_kernel_names

    cfg = T.OlmoeConfig(vocab_size=1024, n_layer=2, n_experts=8, top_k=2)
    scope = Scope()
    with scope_guard(scope), program_guard(Program(), Program()):
        _, _, loss = T.build_olmoe_pretrain(cfg, 1024)
        pt.amp.decorate(opt.AdamWOptimizer(
            learning_rate=1e-4, weight_decay=0.1)).minimize(loss)
        exe = pt.Executor(pt.TPUPlace(0))
        exe.run(pt.default_startup_program(), scope=scope, seed=3)
        feed = make_batch(np.random.RandomState(0), cfg, 1, 1024)
        jax.config.update("jax_dump_ir_to", str(tmp_path))
        try:
            l0, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope)
        finally:
            jax.config.update("jax_dump_ir_to", None)
        l1, = exe.run(feed=feed, fetch_list=[loss.name], scope=scope)
    kernels = collections.Counter(lowered_kernel_names(str(tmp_path)))
    _record("olmoe_toy_step", losses=[float(l0), float(l1)],
            kernels=dict(kernels))
    assert np.isfinite(l0) and np.isfinite(l1), (l0, l1)
    assert kernels["flash_fwd"] == cfg.n_layer, kernels
    assert sum(n for k, n in kernels.items()
               if k.startswith("flash_bwd")) >= cfg.n_layer, kernels


# ---------------------------------------------------------------------------
# CPU: a TPU kind the peak tables do not know is an error, never a default
# ---------------------------------------------------------------------------

class _StubTPU:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


def test_peak_tables_raise_on_unknown_tpu_kind():
    from paddle_tpu.analysis import device_link_bandwidth, device_peak_flops

    with pytest.raises(ValueError, match="TPU v99"):
        device_peak_flops(_StubTPU("TPU v99"))
    with pytest.raises(ValueError, match="TPU v99"):
        device_link_bandwidth(_StubTPU("TPU v99"))


def test_peak_tables_know_the_v5e():
    from paddle_tpu.analysis import device_link_bandwidth, device_peak_flops

    assert device_peak_flops(_StubTPU("TPU v5 lite")) == 197e12
    assert device_link_bandwidth(_StubTPU("TPU v5 lite")) == 200e9
    assert device_peak_flops(_StubTPU("TPU v5")) == 459e12      # a v5p
