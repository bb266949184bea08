"""``models/transformer.py``'s assembled block: ``decoder_block`` builds the
layer a configuration describes (``DecoderConfig.mixer(idx)`` /
``ffn(idx)`` and the keys beside them) out of the parts that exist, and one
loop builds the causal LM over it.  Two things no builder-by-builder test
holds: a decoder NO builder was ever written for trains a step from its
configuration alone, and each of the nine published configurations names the
sequence of sublayers its docstring states."""

import numpy as np
import pytest

from paddle_tpu import layers, optimizer as opt
from paddle_tpu.framework import (Executor, Program, Scope, program_guard,
                                  scope_guard)
from paddle_tpu.models import transformer as T

SEQ = 16


class ChimeraConfig(T.DecoderConfig):
    """A decoder nobody published and no builder wrote: Mamba-2 mixers with
    latent attention every third layer, a leading dense layer, then
    group-limited routed experts beside a shared one, every sublayer between
    two norms.  Only keys: the mixers' own (``mamba2_mixer``'s and
    ``latent_attention``'s) and ``DecoderConfig``'s."""

    sandwich_norm = True
    n_dense_layer = 1
    n_route_group, topk_group = 4, 2
    route_scale = 2.0
    # mamba2_mixer's
    n_mamba_head, d_mamba_head, n_group, d_state = 4, 8, 2, 8
    conv_taps, chunk = 4, 8
    # latent_attention's
    q_lora_rank, kv_lora_rank, d_nope, d_rope, d_v = 12, 8, 8, 4, 8
    rope_theta, rope_scaling = 10000.0, None
    d_inner, d_shared = 40, 24

    def __init__(self):
        super().__init__(vocab_size=64, d_model=32, n_layer=4, n_head=2,
                         d_expert=16, n_experts=8, top_k=2, rms_eps=1e-6)

    def mixer(self, idx):
        return "mla" if idx % 3 == 2 else "mamba2"


def test_a_decoder_no_builder_wrote_trains_a_step_from_its_configuration():
    cfg = ChimeraConfig()
    assert [(cfg.mixer(i), cfg.ffn(i)) for i in range(cfg.n_layer)] == [
        ("mamba2", "dense"), ("mamba2", "routed"), ("mla", "routed"),
        ("mamba2", "routed")]
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        checkpoints = []
        feeds, parts, loss, aux = T._causal_lm(
            cfg, SEQ, fused_head=False, checkpoints=checkpoints)
        opt.SGD(learning_rate=0.1).minimize(loss)
        exe = Executor()
        exe.run(startup, scope=scope, seed=5)
    # what the configuration named is what was built
    kinds = [op.type for op in main.global_block().ops]
    assert (kinds.count("ssd_scan"), kinds.count("flash_attention"),
            kinds.count("moe_ffn")) == (3, 1, 3)
    moe = next(op for op in main.global_block().ops if op.type == "moe_ffn")
    assert (moe.attrs["n_group"], moe.attrs["topk_group"],
            moe.attrs["score_func"]) == (4, 2, "sigmoid")
    names = {p.name for p in main.all_parameters()}
    assert {f"dec_0.ln{i}.w" for i in (1, 2, 3, 4)} <= names   # sandwich
    assert {"dec_0.mamba.in_proj.w", "dec_0.ffn.gate_up.w",
            "dec_1.shared.gate_up.w", "dec_1.moe.select_bias",
            "dec_2.attn.q_b.w", "lm_out.w"} <= names
    assert "dec_0.moe.router.w" not in names and "dec_2.mamba.out.w" \
        not in names
    assert len(checkpoints) == cfg.n_layer and len(aux) == 3
    assert len(parts["expert_load"]) == 3

    rng = np.random.RandomState(0)
    feed = {v.name: rng.randint(1, cfg.vocab_size, (2, SEQ)).astype(np.int64)
            for v in feeds}
    trained = [p.name for p in main.all_parameters() if p.trainable]
    before = {n: np.array(scope.find_var(n)) for n in trained}
    first = float(np.asarray(exe.run(main, feed=feed, scope=scope,
                                     fetch_list=[loss.name])[0]))
    moved = [n for n in trained
             if not np.array_equal(before[n], np.asarray(scope.find_var(n)))]
    second = float(np.asarray(exe.run(main, feed=feed, scope=scope,
                                      fetch_list=[loss.name])[0]))
    assert np.isfinite(first) and np.isfinite(second) and second < first
    assert all(np.isfinite(np.asarray(scope.find_var(n))).all()
               for n in trained)
    # every sublayer differentiates: the step moved each kind of part
    for part in ("word_embedding", "dec_0.mamba.in_proj.w", "dec_0.ln2.w",
                 "dec_0.ffn.down.w", "dec_1.moe.router.w", "dec_1.moe.up.w",
                 "dec_1.shared.down.w", "dec_2.attn.kv_b.w", "dec_3.ln4.w",
                 "final_norm.w", "lm_out.w"):
        assert part in moved, part


def test_a_block_of_one_sublayer_has_one_norm_and_a_tag_may_cover_the_add():
    """``NemotronHConfig``'s blocks through ``decoder_block``: one norm
    named ``norm``; its ``attn`` tag covers the residual add (the key
    ``tag_covers_add``), where Solar-Open2's leaves the add outside."""
    adds = {}
    for cfg, idx in ((T.NemotronHConfig(vocab_size=8, d_model=16,
                                        pattern="*", n_head=2, n_kv_head=1,
                                        d_head=8), 0),
                     (T.SolarOpen2Config(vocab_size=8, d_model=16, n_layer=1,
                                         n_head=2, n_kv_head=1, d_head=8,
                                         n_kda_head=2, d_expert=8,
                                         n_experts=4, top_k=2), 0)):
        main = Program()
        with program_guard(main, Program()):
            x = layers.data("x", shape=[1, SEQ, 16], dtype="float32",
                            append_batch_size=False)
            _, routed = T.decoder_block(x, cfg, idx, attn_impl="base")
        ops = main.global_block().ops
        # the add that puts the mixer's output back beside the block's input
        adds[type(cfg)] = [op.attrs.get("name_scope") for op in ops
                           if op.type == "elementwise_add"
                           and op.inputs["X"] == ["x"]]
        norms = sorted(p.name for p in main.all_parameters()
                       if p.name.endswith((".norm.w", ".ln1.w", ".ln2.w")))
        if isinstance(cfg, T.NemotronHConfig):
            assert norms == ["dec_0.norm.w"] and routed is None
        else:
            assert norms == ["dec_0.ln1.w", "dec_0.ln2.w"]
            assert len(routed) == 3
    assert adds == {T.NemotronHConfig: ["attn"], T.SolarOpen2Config: [None]}


#: configuration -> the (mixer, ffn) of every layer, as its docstring says
PUBLISHED = {
    "olmoe": (T.OlmoeConfig, [("gqa", "routed")] * 16),
    "trinity": (T.TrinityConfig,
                [("gqa", "dense")] * 2 + [("gqa", "routed")] * 30),
    "joyai": (T.JoyaiConfig,
              [("mla", "dense")] + [("mla", "routed")] * 39),
    "xing4": (T.XingConfig,
              [("mla", "dense")] * 2 + [("mla", "routed")] * 38),
    "smallthinker": (T.SmallThinkerConfig, [("gqa", "routed")] * 52),
    "lfm2": (T.Lfm2Config,
             [("gqa" if i in (2, 6, 10, 14, 18, 21) else "conv",
               "dense" if i < 2 else "routed") for i in range(24)]),
    "solar": (T.SolarOpen2Config,
              [("kda" if i % 4 else "gqa", "routed") for i in range(48)]),
    "ling": (T.LingConfig,
             [("mla" if i % 6 == 5 else "kda",
               "dense" if i < 2 else "routed") for i in range(42)]),
    "nemotron3": (T.NemotronHConfig,
                  [{"M": ("mamba2", None), "*": ("gqa", None),
                    "E": (None, "routed")}[c]
                   for c in "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*"
                            "EMEMEMEME"]),
}


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_the_published_pattern_is_what_the_configuration_names(name):
    make, want = PUBLISHED[name]
    cfg = make()
    assert isinstance(cfg, T.DecoderConfig) and cfg.n_layer == len(want)
    assert [(cfg.mixer(i), cfg.ffn(i)) for i in range(cfg.n_layer)] == want
    # where Q and K turn and what they see, for the grouped-query layers
    gqa = [i for i, (m, _) in enumerate(want) if m == "gqa"]
    turns = [i for i in gqa if cfg.rotary(i)]
    windows = {i: cfg.window_at(i) for i in gqa if cfg.window_at(i)}
    if name == "trinity":             # rotary and a window, sliding layers
        sliding = [i for i in range(32) if i % 4 != 3]
        assert turns == sliding and windows == dict.fromkeys(sliding, 2048)
    elif name == "smallthinker":      # full layers first of every four
        sliding = [i for i in range(52) if i % 4]
        assert turns == sliding and windows == dict.fromkeys(sliding, 4096)
    elif name in ("olmoe", "lfm2"):   # rotary everywhere, no window
        assert turns == gqa and not windows
    else:                             # no positional term on a GQA layer
        assert not turns and not windows


def test_the_layers_a_stage_holds_and_the_mtp_modules_number():
    """Ling's pattern follows the PUBLISHED numbers where the program holds
    a run of the layers; JoyAI's MTP module is the family's layer
    ``n_layer``: latent attention over routed experts whatever
    ``n_dense_layer`` says of layer 0."""
    cut = T.LingConfig(n_layer=7, first_layer=1)
    assert [(cut.mixer(i), cut.ffn(i)) for i in range(7)] == [
        ("kda", "dense")] + [("kda", "routed")] * 3 + [("mla", "routed")] \
        + [("kda", "routed")] * 2
    joyai = T.JoyaiConfig(n_layer=2, n_dense_layer=2)
    assert (joyai.mixer(2), joyai.ffn(2)) == ("mla", "routed")
