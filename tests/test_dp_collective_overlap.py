"""The data-parallel step asks XLA:TPU to hide its gradient all-reduces behind
compute (``framework.executor.dp_overlap_options``): which meshes and
platforms get the compile options, that nothing else gets a
``compiler_options`` key at all, ``paddle_tpu_dp_overlap_compiles_total``,
and — compiled here for a described v5e:2x2, no chip — that the TPU compiler
takes every option and leaves all-reduces inside async collective fusions.

The described-topology compile lives in this file alone and loads the TPU
library inside a fixture (one process may hold it)."""

import contextlib
import os
import sys
import types

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import optimizer as opt
from paddle_tpu.framework import (Executor, Program, Scope, executor as E,
                                  program_guard, scope_guard)
from paddle_tpu.models import transformer as T
from paddle_tpu.parallel import mesh as M

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
import dp_arith_check  # noqa: E402  (tools/: where the all-reduces sit)

SEQ, N_MASK, BATCH, DP = 16, 4, 8, 4


def _counts():
    c = E.DP_OVERLAP_CTR
    return {k: c.value(asked=k[0], reason=k[1]) for k in list(c._series)}


def _delta(before):
    return {k: v - before.get(k, 0) for k, v in _counts().items()
            if v - before.get(k, 0)}


def _mesh(axes):
    n = int(np.prod(list(axes.values())))
    return M.make_mesh(axes, jax.devices()[:n])


def _bert(cfg=None):
    cfg = cfg or T.BertConfig(vocab_size=64, d_model=16, n_layer=2, n_head=4,
                              d_inner=32, max_pos=32, dropout=0.0)
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        _, _, loss = T.build_bert_pretrain(
            cfg, SEQ, fused_head=True, arange_pos=True, masked_gather=N_MASK)
        opt.AdamOptimizer(learning_rate=1e-3).minimize(loss)
        exe = Executor()
        exe.run(startup, scope=scope, seed=3)
    return exe, scope, main, loss


def _feed(vocab=64, batch=BATCH, seed=0):
    rng = np.random.RandomState(seed)
    return {"src_ids": rng.randint(1, vocab, (batch, SEQ)).astype(np.int32),
            "mask_pos": np.stack(
                [rng.choice(SEQ, N_MASK, replace=False) + i * SEQ
                 for i in range(batch)]).astype(np.int32),
            "lm_label": rng.randint(1, vocab,
                                    (batch, N_MASK)).astype(np.int32)}


@pytest.fixture
def jit_calls(monkeypatch):
    """The keyword arguments of every ``jax.jit`` the executor module makes
    while the fixture is live."""
    seen, real = [], jax.jit

    def spy(fn, **kwargs):
        seen.append(kwargs)
        return real(fn, **kwargs)

    monkeypatch.setattr(E.jax, "jit", spy)
    return seen


# the rule ------------------------------------------------------------------

@pytest.mark.parametrize("axes,platform,reason", [
    (None, "tpu", "no_mesh"),
    (None, "cpu", "no_mesh"),
    ({"dp": 1}, "tpu", "dp=1"),
    ({"mp": 4}, "tpu", "dp=1"),
    ({"dp": 1, "mp": 2}, "tpu", "dp=1"),
    ({"dp": 4}, "cpu", "not_tpu"),
    ({"dp": 4}, "gpu", "not_tpu"),
    ({"dp": 2, "mp": 2}, "cpu", "not_tpu"),
    ({"dp": 4}, "tpu", "dp_tpu"),
    ({"dp": 2}, "tpu", "dp_tpu"),
    ({"dp": 2, "mp": 2}, "tpu", "dp_tpu"),
])
def test_which_steps_get_the_options(axes, platform, reason):
    """The platform is an argument: the meshes here are CPU devices."""
    before = _counts()
    options, why = E.dp_overlap_options(
        None if axes is None else _mesh(axes), platform)
    assert why == reason
    if reason == "dp_tpu":
        assert options == E._DP_OVERLAP_OPTIONS and options
        assert options is not E._DP_OVERLAP_OPTIONS       # a copy
        assert all(k.startswith("xla_") for k in options)
    else:
        assert options is None
    assert _delta(before) == {}, "deciding counts nothing; building does"


@pytest.mark.parametrize("platform,asked,reason", [
    ("tpu", "1", "dp_tpu"), ("cpu", "0", "not_tpu")])
def test_jit_step_hands_the_options_to_the_jit(jit_calls, platform, asked,
                                               reason):
    """``_jit_step`` over a mesh that says it is of ``platform`` (a stand-in:
    no TPU here, and the CPU compiler rejects ``xla_tpu_*`` names, so the
    jit is built and never called)."""
    real = _mesh({"dp": 4})
    fake = types.SimpleNamespace(
        shape=real.shape, devices=np.array(
            [types.SimpleNamespace(platform=platform)] * 4))
    before = _counts()
    E._jit_step(lambda x: x, fake, donate_argnums=(0,))
    assert _delta(before) == {(asked, reason): 1}
    kwargs, = jit_calls
    assert kwargs.get("donate_argnums") == (0,)
    if asked == "1":
        assert kwargs["compiler_options"] == E._DP_OVERLAP_OPTIONS
    else:
        assert "compiler_options" not in kwargs


# the blocks the executor builds ---------------------------------------------

def _with_dp(n):
    return lambda main, loss: pt.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, places=n)


def _gspmd(main, loss):
    return pt.CompiledProgram(main).with_distributed(
        mesh=_mesh({"dp": 2, "mp": 2}))


@pytest.mark.parametrize("parallel,reason", [
    (None, "no_mesh"),
    (_with_dp(1), "dp=1"),
    (_with_dp(DP), "not_tpu"),
    (_gspmd, "not_tpu"),
])
def test_no_block_here_gets_a_compile_option(jit_calls, parallel, reason):
    """One-device, dp = 1, dp = 4 and dp 2 x mp 2 steps on this CPU: one
    compile counted under its reason, the jit built with no
    ``compiler_options`` keyword, two steps run, and the losses are the
    one-device program's."""
    exe, scope, main, loss = _bert()
    want = [float(np.asarray(exe.run(main, feed=_feed(seed=s), scope=scope,
                                     fetch_list=[loss.name])[0]))
            for s in (0, 1)]
    exe, scope, main, loss = _bert()
    prog = main if parallel is None else parallel(main, loss)
    del jit_calls[:]
    before = _counts()
    got = [float(np.asarray(exe.run(prog, feed=_feed(seed=s), scope=scope,
                                    fetch_list=[loss.name])[0]))
           for s in (0, 1)]
    assert _delta(before) == {("0", reason): 1}, "once a compile, not a step"
    assert len(jit_calls) == 1
    assert "compiler_options" not in jit_calls[0]
    assert ("in_shardings" in jit_calls[0]) == (parallel is not None)
    np.testing.assert_allclose(got, want, rtol=2e-5)


def test_collective_shard_map_block_goes_through_the_same_rule(jit_calls):
    from paddle_tpu import layers
    from paddle_tpu.distributed import GradAllReduce
    eps = ",".join(f"127.0.0.1:{6570 + i}" for i in range(DP))
    main, startup = Program(), Program()
    with program_guard(main, startup), scope_guard(Scope()):
        x = layers.data("x", shape=[8], dtype="float32")
        y = layers.data("y", shape=[1], dtype="int64")
        loss = layers.mean(layers.cross_entropy(
            layers.fc(layers.fc(x, size=16, act="relu"), size=4,
                      act="softmax"), y))
        opt.SGDOptimizer(0.1).minimize(loss)
        GradAllReduce().transpile(rank=0, endpoints=eps,
                                  current_endpoint="127.0.0.1:6570")
        exe = Executor()
        exe.run(startup, seed=42)
        del jit_calls[:]
        before = _counts()
        rng = np.random.RandomState(1)
        lv, = exe.run(feed={"x": rng.rand(16, 8).astype("float32"),
                            "y": rng.randint(0, 4, (16, 1)).astype("int64")},
                      fetch_list=[loss.name])
    assert np.isfinite(np.asarray(lv)).all()
    assert _delta(before) == {("0", "not_tpu"): 1}
    assert len(jit_calls) == 1 and "compiler_options" not in jit_calls[0]


def test_aot_compile_of_a_block_carries_the_jits_options():
    """``_CompiledBlock.__call__``'s HBM-plan path compiles
    ``self.jitted.lower(...).compile()``: the options given to ``jax.jit``
    reach that executable too (shown with an option the CPU compiler knows)."""
    jitted = jax.jit(lambda x: x + 1,
                     compiler_options={"xla_embed_ir_in_executable": True})
    lowered = jitted.lower(np.float32(1))
    assert dict(lowered._lowering._compiler_options_kvs) == {
        "xla_embed_ir_in_executable": True}
    assert float(lowered.compile()(np.float32(1))) == 2.0


# the TPU compiler, no chip --------------------------------------------------

@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                                 # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@contextlib.contextmanager
def _no_compile_cache():
    """Compile for the described chips without reading or writing the
    persistent cache (an executable of a topology is nobody's to reuse)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def test_tpu_compiler_takes_the_options_and_fuses_all_reduces(topo):
    """A 2-layer BERT at widths whose weight gradients pass the combiner's
    threshold, data parallel over the four described chips, compiled with
    what ``dp_overlap_options`` gives a TPU mesh: the compiler knows every
    option, and weight-gradient all-reduces sit inside async collective
    fusions."""
    cfg = T.BertConfig(vocab_size=512, d_model=768, n_layer=2, n_head=12,
                       d_inner=1024, max_pos=32, dropout=0.0)
    exe, scope, main, loss = _bert(cfg)
    prog = pt.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, places=list(topo.devices))

    before = _counts()
    cb, args = dp_arith_check.caught_step(lambda: exe.run(
        prog, feed=_feed(512, 4 * BATCH), scope=scope,
        fetch_list=[loss.name]))
    assert _delta(before) == {("1", "dp_tpu"): 1}
    shapes = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        args, tuple(cb.in_shardings))
    with _no_compile_cache():
        lowered = cb.jitted.lower(*shapes)
        assert dict(lowered._lowering._compiler_options_kvs) == \
            E._DP_OVERLAP_OPTIONS
        _, asked = dp_arith_check.all_reduce_schedule(
            lowered.compile().as_text())
    fused = [r for r in asked if r[2].startswith("fused")]
    assert fused and all(mb >= 1.0 for _, _, _, mb, _ in fused), asked


#: name -> (tokens, model width, experts a token, held experts, router
#: outputs, expert width, the ladder): the four shares that run the held path
HELD_SHARES = {
    "trinity": (8192, 2048, 8, 16, 128, 1024, (16384, 65536)),
    "joyai": (8192, 2048, 8, 16, 256, 768, (8192, 16384, 65536)),
    "lfm2": (16384, 2048, 4, 8, 32, 1792, (65536,)),
    "smallthinker": (16384, 2560, 6, 8, 64, 768, (24576, 98304)),
}


@pytest.mark.parametrize("share", sorted(HELD_SHARES))
def test_tpu_compiler_takes_the_held_paths_ladder(topo, monkeypatch, share):
    """``moe_ffn`` + ``moe_ffn_grad`` over a chip's share of the experts at
    the four cells' real sizes, bf16 through megablox, compiled for one
    described chip: the ladder is the shapes', each row movement that walks
    a rung sits under a conditional of that many branches (none where the
    ladder has one rung: LFM2's), the rows reach the full-length buffers
    through the ``moe_front`` kernel, the grouped matmuls are lowered once,
    not once a rung, and the two un-sorts are the ``moe_held_rows`` kernel
    (PR 42: the compiler takes its row-group copies and SMEM tables), one a
    direction and outside any conditional, where every rung is a long source
    (LFM2's, SmallThinker's); where the first rung is short (Trinity's,
    JoyAI's) the un-sorts keep XLA's gather in two conditionals more."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu import device
    from paddle_tpu.ops import moe_ops
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    S, d, k, held, router_outputs, width, ladder = HELD_SHARES[share]
    assert moe_ops.held_ladder(S, k, held, router_outputs) == ladder
    ctx = types.SimpleNamespace(amp=False, is_abstract=True)
    attrs = {"top_k": k, "score_func": "sigmoid", "norm_topk_prob": True,
             "norm_eps": 1e-20, "route_scale": 2.5, "expert_offset": 0}

    def step(x, d_out, wr, wg, wu, wd):
        ins = {"X": [x], "RouterW": [wr], "GateW": [wg], "UpW": [wu],
               "DownW": [wd]}
        fwd = moe_ops._moe_ffn(ctx, ins, attrs)
        g_ins = {"X$" + n: v for n, v in ins.items()}
        g_ins.update({"Saved": fwd["Saved"], "OG$Out": [d_out]})
        bwd = moe_ops._moe_ffn_grad(ctx, g_ins, attrs)
        return fwd["Out"][0], [v[0] for v in bwd.values()]

    one = SingleDeviceSharding(topo.devices[0])
    shapes = [jax.ShapeDtypeStruct(s, t, sharding=one) for s, t in (
        ((1, S, d), jnp.bfloat16), ((1, S, d), jnp.bfloat16),
        ((d, router_outputs), jnp.float32), ((held, d, width), jnp.float32),
        ((held, d, width), jnp.float32), ((held, width, d), jnp.float32))]
    with _no_compile_cache():
        text = jax.jit(step).lower(*shapes).compile().as_text()
    import re
    conds = re.findall(r"branch_computations=\{([^}]*)\}", text)
    # forward: the row gather, the gate's pass and the weighted sum; grad op:
    # the cotangents, the gate's pass again, its backward and the gather back
    # to tokens.  By the row the two un-sorts leave their switches and the
    # cotangents take two (the weights' before dy's)
    by_rows = moe_ops._rows_unsort(S, k, d, ladder, jnp.bfloat16) is not None
    assert by_rows == (ladder[0] * d * 2 > 16384 * 2048 * 2)
    assert [c.count("%") for c in conds] == [len(ladder)] * (
        0 if len(ladder) == 1 else 6 if by_rows else 7)
    # six fronts a rung below the longest; nine grouped matmuls in all
    assert len(re.findall(r"%moe_front[\w.]* = ", text)) == \
        6 * (len(ladder) - 1)
    assert len(re.findall(r"%(?:jvp_jit_)?t?gmm[\w.]* = ", text)) == 9
    entry = text[text.index("\nENTRY"):]
    assert len(re.findall(r"%moe_held_rows[\w.]* = ", entry)) == \
        len(re.findall(r"%moe_held_rows[\w.]* = ", text)) == \
        (2 if by_rows else 0)


#: name -> (heads, KV heads, T, d_qk, d_v, window, forward blocks or None for
#: the tables' row): the four flash cells' forward calls, and one 2048-wide
#: row that Mosaic's default 16 MiB of scoped VMEM refuses (24 is the least)
FORWARD_CASES = {
    "joyai": (32, 32, 8192, 192, 128, None, None),
    "trinity_full": (32, 4, 8192, 128, 128, None, None),
    "trinity_window": (32, 4, 8192, 128, 128, 2048, None),
    "olmoe": (64, 64, 4096, 128, 128, None, None),
    "smallthinker_window": (28, 4, 16384, 128, 128, 4096, None),
    "joyai_2048_wide": (32, 32, 8192, 192, 128, None, (2048, 1024)),
}


@pytest.mark.parametrize("case", sorted(FORWARD_CASES))
def test_tpu_compiler_takes_the_flash_forward_with_lse_rows(
        topo, monkeypatch, case):
    """``flash_attention_fwd`` at the cells' real shapes, bf16, compiled for
    one described chip: ``lse`` leaves the kernel as ``[bh, 1, Tq]`` float32
    rows (Mosaic takes the in-kernel transposition), no ``[bh, Tq, 128]``
    float32 buffer is left in the module, and the call asks for the VMEM its
    blocks need — which is what lets a 2048-wide row compile at all."""
    import importlib
    import re
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    F = importlib.import_module("paddle_tpu.pallas.flash_attention")
    monkeypatch.setattr(F, "on_tpu", lambda: True)
    h, hk, t, d_qk, d_v, window, blocks = FORWARD_CASES[case]
    asked = []
    reckon = F._fwd_vmem_bytes
    monkeypatch.setattr(F, "_fwd_vmem_bytes", lambda *a, **kw: asked.append(
        reckon(*a, **kw)) or asked[-1])
    one = SingleDeviceSharding(topo.devices[0])
    q, k, v = (jax.ShapeDtypeStruct((1, n, t, w), jnp.bfloat16, sharding=one)
               for n, w in ((h, d_qk), (hk, d_qk), (hk, d_v)))
    kw = dict(causal=True, window=window)
    if blocks:
        kw.update(block_q=blocks[0], block_k=blocks[1])
    assert F.flash_lse_layout(q, k, v, **kw) == "row"
    with _no_compile_cache():
        compiled = jax.jit(lambda q, k, v: F.flash_attention_fwd(
            q, k, v, **kw)).lower(q, k, v).compile()
    text = compiled.as_text()
    call, = [line for line in text.split("\n")
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert f"f32[{h},1,{t}]" in call.split(" custom-call(")[0]
    assert not re.search(rf"f32\[{h},{t},128\]", text)
    assert asked == [reckon(d_qk, d_v, *(blocks or (1024, 1024)), 2)]
    assert 16 << 20 < asked[0] <= F._FUSED_VMEM_SHARE * F._VMEM_BYTES


#: name -> (shape, head_dim, interleaved): what the five decoder cells' steps
#: hand the ``rope`` op, bf16
ROPE_CASES = {
    "trinity_q": ((1, 32, 8192, 128), 128, False),
    "joyai_q_rope": ((1, 32, 8192, 64), 64, True),
    "olmoe_q": ((4, 4096, 2048), 128, False),
    "lfm2_k": ((1, 8, 16384, 64), 64, False),
    "smallthinker_q": ((1, 28, 16384, 128), 128, False),
}


@pytest.mark.parametrize("transpose", [False, True])
@pytest.mark.parametrize("case", sorted(ROPE_CASES))
def test_tpu_compiler_takes_the_rope_kernel(topo, case, transpose):
    """``pallas/rope.py`` at the cells' real shapes, compiled for one
    described chip, the rotation and its transpose: Mosaic takes the lane
    rotations at 64 and 128 lanes, both pairings and both ranks; the module
    is one custom call beside the tables' fusions, and where a head fills
    whole tiles it has no copy, no stand-alone convert and no temporary (a
    64-wide tensor alone lies with T in the lanes at the module's edge,
    which a step's neighbours do not ask for: PERF.md section 5, PR 43)."""
    import re
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.pallas import rope as kernel
    shape, head_dim, interleaved = ROPE_CASES[case]
    assert kernel.fits(shape, head_dim, jnp.bfloat16)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                             sharding=SingleDeviceSharding(topo.devices[0]))
    with _no_compile_cache():
        compiled = jax.jit(lambda v: kernel.rope(
            v, head_dim, 10000.0, interleaved, transpose=transpose)
        ).lower(x).compile()
    text = compiled.as_text()
    entry = text[text.index("\nENTRY"):]
    assert len(re.findall(r" custom-call\(.*tpu_custom_call", entry)) == 1
    if head_dim % 128 == 0:
        assert not re.search(r" = \S+ (copy|convert)\(", entry)
        assert compiled.memory_analysis().temp_size_in_bytes == 0


#: name -> T: the two latent-attention cells' flash calls since PR 48, 32
#: heads of 128 + 64 over 128-wide values, ONE rotary key head, bf16
TWO_PRODUCT_CASES = {"joyai": 8192, "xing4": 4096}


@pytest.mark.parametrize("half", ["forward", "backward"])
@pytest.mark.parametrize("case", sorted(TWO_PRODUCT_CASES))
def test_tpu_compiler_takes_the_two_product_kernels(topo, monkeypatch, case,
                                                    half):
    """The flash kernels with the score as two products (``q_rope``,
    ``k_rope``) at the cells' real shapes, compiled for one described chip:
    Mosaic takes the 64-wide blocks, scratch and the ``[Tq, 64]`` dQRope
    accumulator; the forward is one custom call that reads the ``[1, T,
    64]`` rotary key as it is, and the module holds nothing 192 wide and no
    32-head copy of that key; the backward is the fused kernel, asks for the
    VMEM its shapes need, and the only ``[32, T, 64]`` it writes beside
    dQRope is the per-head dKRope partial that the sum outside folds to one
    head."""
    import importlib
    import re
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    F = importlib.import_module("paddle_tpu.pallas.flash_attention")
    monkeypatch.setattr(F, "on_tpu", lambda: True)
    t, h = TWO_PRODUCT_CASES[case], 32
    one = SingleDeviceSharding(topo.devices[0])

    def s(n, w, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((1, n, t, w), dtype, sharding=one)
    q, k, v, qr, kr = s(h, 128), s(h, 128), s(h, 128), s(h, 64), s(1, 64)
    kw = dict(causal=True, sm_scale=192 ** -0.5)
    assert F.flash_lse_layout(q, k, v, q_rope=qr, k_rope=kr, **kw) == "row"
    assert F.flash_bwd_kernel(q, k, v, interpret=True, q_rope=qr, k_rope=kr,
                              **kw) == "fused"
    with _no_compile_cache():
        if half == "forward":
            compiled = jax.jit(lambda q, k, v, qr, kr: F.flash_attention_fwd(
                q, k, v, q_rope=qr, k_rope=kr, **kw)).lower(
                    q, k, v, qr, kr).compile()
        else:
            lse = jax.ShapeDtypeStruct((1, h, t), jnp.float32, sharding=one)
            compiled = jax.jit(
                lambda q, k, v, o, lse, do, qr, kr: F.flash_attention_bwd(
                    q, k, v, None, o, lse, do, q_rope=qr, k_rope=kr, **kw)
            ).lower(q, k, v, v, lse, v, qr, kr).compile()
    text = compiled.as_text()
    calls = [line for line in text.split("\n")
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert len(calls) == 1, calls
    assert not re.search(r"\[(1,)?32,\d+,(192|256)\]", text)
    if half == "forward":
        assert f"bf16[1,{t},64]" in calls[0]        # the key, one head
        assert not re.search(rf"bf16\[(1,)?32,{t},64\]\S* broadcast", text)
    else:
        assert "flash_bwd_fused" in calls[0]
        # dQ, dK, dV, dKRope a head, dQRope: five results of one call
        results = calls[0].split(" custom-call(")[0]
        assert len(re.findall(rf"bf16\[32,{t},128\]", results)) == 3
        assert len(re.findall(rf"bf16\[32,{t},64\]", results)) == 2


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("heads,neg_eigval", [(8, True), (16, False)],
                         ids=["solar_open2", "ling3"])
def test_tpu_compiler_takes_the_kda_kernels_at_the_cells_shapes(
        topo, monkeypatch, heads, neg_eigval, dtype):
    """``kda_scan`` + ``kda_scan_grad`` at Solar-Open2's [1, 8192, 8, 128]
    with beta doubled and at Ling's [1, 8192, 16, 128] with beta as it
    comes, in chunks of 64, lowered as on a TPU and compiled for one
    described chip: Mosaic takes the forward kernel and the hand-written
    backward one (interpret mode, ``tests/test_kda_kernel.py``, says nothing
    about that), the grad op holds no forward kernel and no loop, and beyond
    ``States`` (67 MB at 8 heads) the bf16 pair has no temporary in HBM but
    the padded ``[t, heads]`` blocks of Beta and dBeta."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu import device
    from paddle_tpu.ops import kda_ops
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    ctx = types.SimpleNamespace(amp=False, is_abstract=True)
    attrs = {"chunk": 64, "neg_eigval": neg_eigval}
    slots = ("Q", "K", "V", "G", "Beta")

    def forward(*prim):
        return kda_ops._kda_scan(ctx, {s: [x] for s, x in zip(slots, prim)},
                                 attrs)

    def step(d_out, *prim):
        fwd = forward(*prim)
        ins = {"X$" + s: [x] for s, x in zip(slots, prim)}
        ins.update({"States": fwd["States"], "OG$Out": [d_out]})
        return fwd["Out"][0], [v[0] for v in kda_ops._kda_scan_grad(
            ctx, ins, attrs).values()]

    def backward(d_out, states, *prim):
        ins = {"X$" + s: [x] for s, x in zip(slots, prim)}
        ins.update({"States": [states], "OG$Out": [d_out]})
        return [v[0] for v in kda_ops._kda_scan_grad(ctx, ins,
                                                     attrs).values()]

    one = SingleDeviceSharding(topo.devices[0])

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one)
    shape = (1, 8192, heads, 128)
    x, g, beta = s(shape, dtype), s(shape, "float32"), s(shape[:3], "float32")
    states = s((1, heads, 128, 128, 128), "float32")
    with _no_compile_cache():
        both = jax.jit(step).lower(x, x, x, x, g, beta).compile()
        back = jax.jit(backward).lower(x, states, x, x, x, g, beta).compile()
    text = both.as_text()
    assert text.count('"kda_fwd"') == 1 and text.count('"kda_bwd"') == 1, \
        [line for line in text.splitlines() if "custom_call_target" in line]
    assert "kda_fwd" not in back.as_text() and " while(" not in back.as_text()
    if dtype == "bfloat16":     # float32 streams are copied into the
        # [t, h d] tiling first; the cell's are bf16
        # at 16 heads a call that stands alone also copies dQ, dK and dV
        # (34 MB each) out of the kernel's [t, h d] tiling into the entry
        # result's [t, h, d] one; at 8 heads that is a bitcast
        copies = (3 * 34 << 20) * (heads == 16)
        assert both.memory_analysis().temp_size_in_bytes < copies + (
            67 * heads // 8 + 32 << 20)
        assert back.memory_analysis().temp_size_in_bytes < copies + (16 << 20)


@pytest.mark.parametrize("d,bias", [(6144, False), (6144, True),
                                    (3072, False)],
                         ids=["ling3", "nemotron3", "solar_open2"])
def test_tpu_compiler_takes_the_short_conv_kernels_at_the_cells_shapes(
        topo, monkeypatch, d, bias):
    """The ungated ``short_conv`` + ``short_conv_grad`` at [1, 8192, 6144]
    bf16 with Nemotron's bias and without (Ling's) and at Solar-Open2's
    3072 channels, four taps, lowered as on a TPU and compiled for one
    described chip: Mosaic takes both kernels (interpret mode,
    ``tests/test_short_conv_kernel.py``, says nothing about that: the
    sublane rotations, the halo blocks, the revisited sums), one of each and
    nothing of the ``jax.numpy`` form beside them: no float32 copy of a
    stream in HBM, only the filter's partial sums (1 MB)."""
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu import device
    from paddle_tpu.ops import sequence_ops
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    ctx = types.SimpleNamespace(amp=False, is_abstract=True)
    attrs = {"gated": False}

    def step(x, w, b, d_out):
        fwd = sequence_ops._short_conv(
            ctx, {"X": [x], "Filter": [w], "Bias": [b]}, attrs)
        back = sequence_ops._short_conv_grad(
            ctx, {"X$X": [x], "X$Filter": [w], "X$Bias": [b],
                  "OG$Out": [d_out]}, attrs)
        return fwd["Out"][0], [v[0] for v in back.values()]

    one = SingleDeviceSharding(topo.devices[0])

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one)
    x = s((1, 8192, d), "bfloat16")
    with _no_compile_cache():
        both = jax.jit(step).lower(
            x, s((d, 4), "float32"), s((d,), "float32") if bias else None,
            x).compile()
    text = both.as_text()
    assert text.count('"short_conv_fwd"') == 1 and \
        text.count('"short_conv_bwd"') == 1, \
        [line for line in text.splitlines() if "custom_call_target" in line]
    assert len(jax.tree_util.tree_leaves(both.out_info)) == 3 + bias
    assert both.memory_analysis().temp_size_in_bytes < 4 << 20


@pytest.mark.parametrize("half", ["forward", "backward"])
def test_tpu_compiler_takes_the_ssd_kernels_at_the_cells_shapes(
        topo, monkeypatch, half):
    """``ssd_scan`` and ``ssd_scan_grad`` at Nemotron-3-Nano's [1, 8192, 64,
    64] bf16 with 8 groups of state 128, float32 Dt and a DtBias, lowered as
    on a TPU and compiled for one described chip: Mosaic takes each kernel
    (interpret mode, ``tests/test_ssd_kernel.py``, says nothing about that:
    the transposes, the dynamic sublane slices, the revisited blocks), ONE
    custom call an op, the streams read as the program's ``[b, t, .]``
    tensors lie (the reshapes to the op's four axes and back are bitcasts),
    no copy or transpose of a state-shaped or ``[., 128, 128]``-shaped
    operand beside it, and no temporary in HBM but the backward's two
    ``[t, H]`` float32 streams and the partial rows of dD."""
    import re
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu import device
    from paddle_tpu.ops import ssd_ops
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    ctx = types.SimpleNamespace(amp=False, is_abstract=True)
    attrs = {"chunk": 128}
    slots = ("X", "Dt", "ALog", "B", "C", "D", "DtBias")
    b, t, h, p, g, n = 1, 8192, 64, 64, 8, 128

    def shaped(x, dt, a_log, bm, cm, d, bias):
        return (x.reshape(b, t, h, p), dt, a_log, bm.reshape(b, t, g, n),
                cm.reshape(b, t, g, n), d, bias)

    def forward(*prim):
        out = ssd_ops._ssd_scan(
            ctx, {s: [v] for s, v in zip(slots, shaped(*prim))}, attrs)
        return out["Out"][0].reshape(b, t, h * p), out["States"][0]

    def backward(d_out, states, *prim):
        ins = {"X$" + s: [v] for s, v in zip(slots, shaped(*prim))}
        ins.update({"States": [states],
                    "OG$Out": [d_out.reshape(b, t, h, p)]})
        grads = ssd_ops._ssd_scan_grad(ctx, ins, attrs)
        return [v[0].reshape(b, t, -1) if v[0].ndim == 4 else v[0]
                for v in grads.values()]

    one = SingleDeviceSharding(topo.devices[0])

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one)
    x, bc = s((b, t, h * p), "bfloat16"), s((b, t, g * n), "bfloat16")
    per = s((h,), "float32")
    prim = (x, s((b, t, h), "float32"), per, bc, bc, per, per)
    states = s((b, h, t // 128, p, n), "float32")
    with _no_compile_cache():
        if half == "forward":
            compiled = jax.jit(forward).lower(*prim).compile()
        else:
            compiled = jax.jit(backward).lower(x, states, *prim).compile()
    text = compiled.as_text()
    name = {"forward": "ssd_fwd", "backward": "ssd_bwd"}[half]
    calls = [line for line in text.split("\n")
             if "custom-call(" in line and "tpu_custom_call" in line]
    assert len(calls) == 1 and f'"{name}"' in text, calls
    assert " while(" not in text
    moved = [line for line in text.split("\n")
             if re.search(r" (copy|transpose)\(", line)
             and re.search(r"\[[\d,]*(64,128|128,128|128,64)\]", line)]
    assert moved == [], moved
    # backward: dDelta and dDA [8192, 64] float32, 2 MB each, and their
    # elementwise children before the sums
    limit = (1 << 20) if half == "forward" else (12 << 20)
    assert compiled.memory_analysis().temp_size_in_bytes < limit
