"""The SDAR-30B-A3B cell (``sdar_30b_a3b_bd_s8192_r64``) rehearsed on the CPU
at toy widths: its files, entries and metrics picked BY NAME (never by
position) and held by MEMBERSHIP (a later cell may join the same lists), the
configuration file against the catalog row, the parameter count from the
program, ``sdar_flops``'s live pairs against a brute count of the mask, the
FLOPs by part by hand, the two new readers on hand-made inputs and with
nothing to read, the cell end to end to the contract's last line, what the
traffic draws, planted faults against the cell's own limits, what the lowered
step names and counts, and the parent commit on the new cell's name.  Nothing
here is a speed number."""

import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, sdar_flops  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_benchmark_rehearsal as rehearsal  # noqa: E402
import test_op_scopes as scopes_test  # noqa: E402
import test_program_scopes as program_scopes_test  # noqa: E402

CELL = "sdar_30b_a3b_bd_s8192_r64"
CONFIG = "sdar_30b_a3b"
TRAFFIC_NAME = "bd_s8192_b4_r64"
SPEC = harness.load_spec()
FILE = harness.load_json(f"benchmark/configs/{CONFIG}.json")
TRAFFIC = harness.load_traffic(TRAFFIC_NAME)
#: the two per-layer metrics this cell brings, each a reader and an entry
NEW = ("flash_dead_tile_share", "bd_stream_device_ms.train")
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
#: the per-layer lists the cell joins: the six ISSUE 61 names, the memory
#: plan's four, and what else of a training step finds something to read
LISTS = NEW + (
    "flash_roofline", "attention_device_ms.train", "moe_device_ms.train",
    "train_mfu",
    "hbm_step_arguments_gb.train", "hbm_step_temporaries_gb.train",
    "hbm_step_unaliased_outputs_gb.train", "hbm_outside_step_gb.train",
    "step_device_ms.train", "train_device_idle_share", "dispatch_ms.train",
    "op_scoped_share.train", "fwd_device_ms.train", "bwd_device_ms.train",
    "opt_device_ms.train", "lm_head_device_ms.train",
    "moe_dispatch_device_ms.train", "moe_router_device_ms.train",
    "recompute_device_ms.train", "xla_remat_device_ms.train",
    "vjp_forward_again_device_ms.train")


def toy_sdar(**traffic):
    c = copy.deepcopy(FILE)
    c.update(hidden_size=64, num_attention_heads=8, num_key_value_heads=1,
             head_dim=16, moe_intermediate_size=32, num_experts=4,
             num_experts_per_tok=2, num_hidden_layers=2, vocab_size=128)
    c["assumed"].update(router_outputs=16, expert_offset=4,
                        mask_token_id=127)
    # toy widths: the fused head's bf16 products move the loss by 1e-4 and
    # bf16 AMP by 1e-2; the chip's limits are set at the real widths
    c["loss_tolerance"] = {"relative": 2e-3, "hidden_relative": 1e-3,
                           "top_k_differ_share": 0.02,
                           "first_hidden_relative": 8e-2,
                           "first_gradient_rest_relative": 0.25,
                           "first_gradient_experts_relative": 0.3,
                           "first_gradient_experts_worst_relative": 0.5,
                           "first_training_loss_relative": 2e-2,
                           "first_gradient_router_relative": 0.5,
                           "first_gradient_attention_relative": 0.3,
                           "first_gradient_all_relative": 0.2,
                           "replayed_update_relative": 6e-3,
                           "reason": "toy widths"}
    t = copy.deepcopy(TRAFFIC)
    t.update(batch_per_chip=2, seq_len=40, ring=2, warmup_steps=1,
             check_batch=2, reference_q_block=16, mask_token_id=127)
    t.update(traffic)
    return c, t


# -- BENCHMARK.json ----------------------------------------------------------

def test_the_cell_is_listed_with_its_files_and_metrics():
    cell = harness.find(SPEC["workloads"], CELL, "workload")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, TRAFFIC_NAME, 1)
    assert len(cell["why"]) <= 200 and "TODO" not in cell["why"]
    cfg = harness.find(SPEC["configs"], CONFIG, "config")
    assert FILE["reduced"] == cfg["reduced"] == REDUCED
    assert FILE["source"] == cfg["source"] and len(cfg["why"]) <= 200
    assert cfg["file"] == f"benchmark/configs/{CONFIG}.json"
    assert FILE["flops_module"] == "sdar_flops"
    for kind, fn in (("models", "build_train"), ("reference", "loss")):
        assert callable(getattr(harness.load_module(kind, CONFIG), fn))
    assert TRAFFIC["kind"] == "train_ring"          # no generator is added
    e2e = {m["name"] for m in harness.metrics_of_cell(SPEC, "end_to_end",
                                                      CELL)}
    assert e2e == {"train_samples_per_s", "peak_hbm_gb", "setup_s"}
    layer = harness.metrics_of_cell(SPEC, "per_layer", CELL)
    names = {m["name"] for m in layer}
    assert names >= {"first_step_program_s", "first_step_backend_s",
                     "retrace_s", "train_step_cache_misses"}
    # a per-layer metric's cell reports the end-to-end metric it moves
    assert {m["moves"] for m in layer} <= e2e
    # membership, by name: the cell is on every list the issue names; what
    # else those lists hold is theirs
    listed = {m["name"] for m in SPEC["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed >= set(LISTS)
    # the two new metrics: a reader each, an entry each, this cell's alone
    for name in NEW:
        assert callable(harness.load_module("layer_metrics", name).read)
        entry = harness.find(SPEC["per_layer"], name, "metric")
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "train_samples_per_s"
    for key in ("why_sizes", "recompute_why"):
        assert TRAFFIC[key] and "PROVISIONAL" not in TRAFFIC[key]
    for key, v in FILE["loss_tolerance"].items():
        assert "PROVISIONAL" not in str(v), key


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog beside the guide on this machine")
    with open(path) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row, = [r for r in rows if r["name"] == "SDAR-30B-A3B-Chat"]
    return row


PUBLISHED = {"num_hidden_layers": 48, "num_experts": 128,
             "vocab_size": 151936}


def test_the_config_file_holds_the_catalogs_numbers():
    """Every key of the catalog row's ``config`` is in the file under its
    name, equal unless listed in ``reduced``; what the catalog lists under
    ``not_given`` is under ``assumed`` with its reason."""
    row = _catalog()
    assert FILE["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert key in FILE, key
        if key in REDUCED:
            assert value == PUBLISHED[key] and FILE[key] != value, key
        else:
            assert FILE[key] == value, key
    assert (FILE["num_hidden_layers"], FILE["num_experts"],
            FILE["vocab_size"]) == (6, 16, 18992)
    assert FILE["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert FILE["num_experts"] * 8 == PUBLISHED["num_experts"] == \
        FILE["assumed"]["router_outputs"]
    assert set(row["not_given"]) == {"block length", "noise schedule"}
    a = FILE["assumed"]
    assert a["block_length"] == 4 == TRAFFIC["block_length"]
    assert a["mask_token_id"] == 18991 == FILE["vocab_size"] - 1 == \
        TRAFFIC["mask_token_id"]
    for key in ("block_length_note", "noise_schedule", "no_shift",
                "mask_token_id_note", "loss_normalisation", "equations",
                "parameters", "reduced_note"):
        assert len(a[key]) > 60, key
    assert "645,623,296" in a["parameters"]
    assert "8 chips" in FILE["deployment"]


def test_the_startup_program_makes_the_stated_initial_values():
    """``assumed.initial_scale``: the table's draw 100 times as wide and
    every attention output projection's a tenth, in the startup program
    itself (so whatever runs it again makes the same state), nothing else
    touched; a toy row routes by its own token, not its context's mean."""
    adapter = harness.load_module("models", CONFIG)
    scales = FILE["assumed"]["initial_scale"]
    assert scales == {"word_embedding": 100.0, "attn.out.w": 0.1}
    assert "initial_scale" in FILE["assumed"]["initial_values"]
    config, traffic = toy_sdar()

    def widths(config):
        m = adapter.build_train(config, traffic, 3, 1, False)
        return {v.name: float(np.asarray(m["scope"].find_var(v.name)).std())
                for v in m["parameters"]}

    plain = copy.deepcopy(config)
    plain["assumed"]["initial_scale"] = {}
    got, was = widths(config), widths(plain)
    for name, w in was.items():
        factor = [f for key, f in scales.items() if name.endswith(key)]
        assert got[name] == pytest.approx(w * (factor[0] if factor else 1),
                                          rel=1e-5), name
    assert sum(n.endswith("attn.out.w") for n in was) == 2


def test_the_parameters_are_645_623_296_counted_from_the_program():
    """645,623,296 parameters at 16 bytes: 10.33 GB, from the shapes the
    program holds and, by part, from ``sdar_flops.parameters``; a layer
    94,638,336."""
    from paddle_tpu.framework import Program, program_guard
    from paddle_tpu.models import transformer as T
    cfg = harness.load_module("models", CONFIG).sdar_config(FILE)
    assert (cfg.n_layer, cfg.n_held, cfg.n_experts, cfg.block_diffusion,
            cfg.mask_token_id) == (6, 16, 128, 4, 18991)
    main = Program()
    with program_guard(main, Program()):
        T.build_sdar_pretrain(cfg, 8192)
    shapes = {p.name: tuple(p.shape) for p in main.all_parameters()}
    assert shapes["dec_0.attn.qkv.w"] == (2048, 4096 + 2 * 512)
    assert shapes["dec_0.attn.out.w"] == (4096, 2048)
    assert shapes["dec_0.attn.q_norm.w"] == shapes["dec_0.attn.k_norm.w"] \
        == (128,)
    assert shapes["dec_0.ln1.w"] == shapes["dec_0.ln2.w"] == (2048,)
    assert shapes["dec_0.moe.router.w"] == (2048, 128)
    assert shapes["dec_0.moe.gate.w"] == shapes["dec_0.moe.up.w"] == \
        (16, 2048, 768)
    assert shapes["dec_0.moe.down.w"] == (16, 768, 2048)
    assert shapes["word_embedding"] == shapes["lm_out.w"][::-1] \
        == (18992, 2048)
    # no bias, no gate on the attention, no shared expert, no selection bias
    assert not any(n.endswith((".b", "select_bias")) or ".shared." in n
                   for n in shapes)

    def block(i):
        return sum(int(np.prod(s)) for n, s in shapes.items()
                   if n.startswith(f"dec_{i}."))
    assert [block(i) for i in range(6)] == [94_638_336] * 6
    n = sum(int(np.prod(s)) for s in shapes.values())
    by_part = sdar_flops.parameters(FILE)
    assert n == sum(by_part.values()) == 645_623_296
    assert by_part["experts"] == 6 * 75_497_472
    assert by_part["embedding_and_head"] == 77_791_232
    assert round(16 * n / 1e9, 2) == 10.33
    assert 16 * n / 16.9e9 > 0.25
    # five layers, the depth the issue falls back to: 550,984,960
    assert n - 94_638_336 == 550_984_960


# -- the yardstick's arithmetic ----------------------------------------------

def _brute_mask(seq, block):
    t = 2 * seq
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    bi, bj = (i % seq) // block, (j % seq) // block
    return ((i < seq) & (j < seq) & (bi == bj)) | \
        ((i < seq) & (j >= seq) & (bj < bi)) | \
        ((i >= seq) & (j >= seq) & (bj <= bi))


@pytest.mark.parametrize("seq, block", [(96, 4), (96, 1), (96, 32),
                                        (100, 4), (36, 3)])
def test_the_live_pairs_against_a_brute_count_of_the_mask(seq, block):
    """``sdar_flops.live_pairs`` and the kernels' own mask
    (``BlockDiffusion.dense``) are both the four lines of the issue, counted
    pair by pair; and the tile pairs the kernels run cover them."""
    from paddle_tpu.pallas.flash_attention import block_diffusion
    mask = _brute_mask(seq, block)
    assert sdar_flops.live_pairs(seq, block) == mask.sum() == \
        seq * seq + seq * block
    form = block_diffusion(2 * seq, block)
    assert (np.asarray(form.dense(2 * seq, 2 * seq)) == mask).all()
    pairs = form.tile_pairs(32, 32)
    n = -(-2 * seq // 32)
    assert sum(pairs.values()) == n * n
    tiles = [mask[a:a + 32, b:b + 32] for a in range(0, 2 * seq, 32)
             for b in range(0, 2 * seq, 32)]
    assert pairs["dead"] == sum(not t.any() for t in tiles)


def test_the_published_size_by_hand():
    """The issue's reckoning, before any code: live pairs a head 6.714e7;
    forward FLOPs a layer: flash 1.10e12, projections 6.18e11, held experts
    1.55e11; six layers and the head over 8192 rows: 1.19e13, flash 55 %;
    and the tile pairs of the cell's grid."""
    from paddle_tpu.pallas.flash_attention import block_diffusion
    assert sdar_flops.live_pairs(8192, 4) == 8192 ** 2 + 8192 * 4 == 67141632
    by = sdar_flops.forward_flops_by_part(FILE, 8192)
    assert by["attention_scores"] / 6 == pytest.approx(1.10e12, rel=5e-3)
    assert by["attention_projections"] / 6 == pytest.approx(6.18e11, rel=2e-3)
    assert by["routed_experts"] / 6 == 6.0 * 16384 * 2048 * 768 == \
        pytest.approx(1.55e11, rel=5e-3)
    assert by["head"] == pytest.approx(6.4e11, rel=6e-3)
    total = sum(by.values())
    assert total == pytest.approx(1.19e13, rel=3e-3)
    assert by["attention_scores"] / total == pytest.approx(0.55, abs=5e-3)
    assert sdar_flops.train_flops_per_sample(FILE, 8192) == 3 * total
    fwd, bwd = sdar_flops.flash_layer_kernels(FILE, 8192)
    assert fwd[0] == 4 * 128 * 32 * 67141632 and bwd[0] == 2 * fwd[0]
    assert fwd[1] == 2 * 32 * 16384 * 128 * 2 + 2 * 4 * 16384 * 128 * 2 \
        + 32 * 16384 * 4
    assert len(sdar_flops.flash_work(FILE, TRAFFIC)) == 12
    nine = sdar_flops.held_experts_work(FILE, TRAFFIC)
    assert len(nine) == 54 and nine[0][0] == 2.0 * 16384 * 2048 * 768
    assert block_diffusion(16384, 4).tile_pairs(1024, 1024) == \
        {"free": 56, "masked": 24, "dead": 176}


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _inputs(tmp_path, events, steps=2, config=FILE):
    inputs = scopes_test._inputs(tmp_path, events, steps)
    inputs.update(config=config, traffic={"seq_len": 8192}, peaks=PEAKS,
                  facts={"batch": 1, "chips": 1})
    return inputs


def _read(metric, inputs):
    return harness.load_module("layer_metrics", metric).read(inputs)


def test_the_readers_on_hand_made_inputs(tmp_path):
    from paddle_tpu.ops import attention_ops
    fwd, bwd, rc = ("jit(step)/pt.%s/" % r for r in ("fwd", "bwd", "rc"))
    inputs = _inputs(tmp_path, [
        ("fusion.1", fwd + "concat/bd_stream/concatenate:", 0, 40),
        ("fusion.2", fwd + "flash_attention/attn/block_diffusion/flash_fwd:",
         40, 200),
        ("fusion.3", rc + "reshape2/attn.bd_stream/copy:", 240, 20),
        ("fusion.4", bwd + "flash_attention_grad/attn/block_diffusion/"
         "flash_bwd_fused:", 260, 400),
        ("fusion.5", bwd + "slice_grad/bd_stream/pad:", 660, 60),
        ("fusion.6", fwd + "mul/attn/dot_general:", 720, 100),
    ])
    # 2 steps: 120 ns under the tag, 600 under the two flash ops
    assert _read("bd_stream_device_ms.train", inputs) == \
        pytest.approx(60e-9 * 1e3)
    least = sum(max(fl / 197e12, by / 819e9)
                for fl, by in sdar_flops.flash_work(FILE, TRAFFIC))
    assert _read("flash_roofline", inputs) == \
        pytest.approx(100 * least / 300e-9)
    # the counter: differences, whatever this process lowered before
    ctr = attention_ops.FLASH_TILE_PAIRS_CTR
    for state, n in (("free", 56), ("masked", 24), ("dead", 176)):
        ctr.inc(n * 1e6, mask="block_diffusion", block="4", state=state,
                **{"pass": "fwd"})
    ctr.inc(1e9, mask="block_diffusion", block="4", state="dead",
            **{"pass": "bwd"})               # the backward's is not read
    assert _read("flash_dead_tile_share", inputs) == \
        pytest.approx(68.75, abs=0.01)


def test_the_readers_return_nothing_with_nothing_to_read(tmp_path):
    """A trace of another program (the parent's: no ``bd_stream`` tag), a
    trace without scopes, no trace at all; and a registry without the
    counter, as the parent's is."""
    from paddle_tpu import monitor
    other = _inputs(tmp_path, [
        ("fusion.1", "jit(step)/pt.fwd/mul/attn/dot_general:", 0, 100)])
    (tmp_path / "b").mkdir()
    bare = _inputs(tmp_path / "b", [("fusion.1", None, 0, 100)])
    none = dict(other, trace=None, trace_window=None)
    for inputs in (other, bare, none):
        assert _read("bd_stream_device_ms.train", inputs) is None
    reader = harness.load_module("layer_metrics", "flash_dead_tile_share")
    real = monitor.REGISTRY

    class Without:
        def get(self, name):
            return None
    try:
        monitor.REGISTRY = Without()
        assert reader.read(other) is None
    finally:
        monitor.REGISTRY = real


@pytest.mark.parametrize("metric", NEW[1:])
def test_reader_without_inputs_returns_nothing(metric):
    empty = {"spans": [], "counters": {}, "e2e": {}, "trace": None,
             "facts": {"batch": 1, "chips": 1, "flops_per_sample": 1.0,
                       "samples_per_s": 1.0},
             "trace_window": None, "config": {}, "traffic": {},
             "peaks": None, "chips": 1}
    assert harness.load_module("layer_metrics", metric).read(empty) is None


# -- the cell end to end -----------------------------------------------------

def test_cell_end_to_end_on_cpu():
    config, traffic = toy_sdar()
    assert traffic["recompute"] is True          # as the chip runs it
    result = harness.run_cell(CELL, seed=rehearsal.BIG_SEED, seconds=0.5,
                              trace=True, on_chip=False, config=config,
                              traffic=traffic, spec=SPEC)
    line = rehearsal.check_contract_line(result, CELL, 1)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"     # and so: not a result
    assert line["compared"][-1].startswith("correct: ")
    detail = line["compared"][1]
    assert "gradient against jax.grad of the reference" in detail
    assert "attention: worst leaf" in detail
    assert "a state left unchanged reads 1" in detail
    assert "limits exceeded: none" in detail
    # a traced line holds, of what moves the rate, what a CPU run can read:
    # the counter's share of dead tile pairs (the device times and the
    # shares of a peak come from the chip's trace alone)
    assert 0 <= line["metrics"]["flash_dead_tile_share"]["value"] < 100
    assert not set(line["metrics"]) & {
        "flash_roofline", "step_device_ms.train",
        "bd_stream_device_ms.train"}


@pytest.mark.slow
def test_cell_end_to_end_on_cpu_without_recomputation():
    config, traffic = toy_sdar(recompute=False)
    result = harness.run_cell(CELL, seed=7, seconds=0.5, trace=False,
                              on_chip=False, config=config, traffic=traffic,
                              spec=SPEC)
    assert rehearsal.check_contract_line(result, CELL, 0)["correct"] is True


# -- what the traffic draws --------------------------------------------------

def _built(seed, **traffic):
    config, t = toy_sdar(**traffic)
    model = harness.load_module("models", CONFIG)
    return model, config, t, model.build_train(config, t, seed, 1, False)


def test_the_feeds_are_the_objectives_and_the_seed_is_the_traffic():
    """The weights are the model (``weights_seed``), ``--seed`` draws the
    feeds; a noisy id is the clean id or the mask id; the label is the clean
    id exactly where the noisy id is the mask id; the weight is ``1 / t`` of
    the block, one value a block, within the schedule's range; no document
    token is 0 or the mask id."""
    _, config, t, a = _built(3, seq_len=64)
    _, _, _, b = _built(4, seq_len=64)
    names = [v.name for v in a["parameters"]]
    for n in names[:6]:
        assert np.array_equal(np.asarray(a["scope"].find_var(n)),
                              np.asarray(b["scope"].find_var(n))), n
    fa, fb = a["ring"][0], b["ring"][0]
    assert sorted(fa) == ["clean_ids", "lm_label", "loss_weight", "noisy_ids"]
    assert not np.array_equal(fa["clean_ids"], fb["clean_ids"])
    mask_id = config["assumed"]["mask_token_id"]
    for f in a["ring"]:
        clean, noisy, label, w = (f[k] for k in (
            "clean_ids", "noisy_ids", "lm_label", "loss_weight"))
        assert clean.min() >= 1 and clean.max() < mask_id
        masked = noisy == mask_id
        assert np.array_equal(noisy[~masked], clean[~masked])
        assert np.array_equal(label, np.where(masked, clean, 0))
        blocks = w.reshape(w.shape[0], -1, t["block_length"])
        assert (blocks == blocks[..., :1]).all()
        assert w.min() >= 1.0 and w.max() <= 1.0 / t["noise_t_min"]
        assert 0 < masked.mean() < 1


@pytest.mark.parametrize("fault", [None, "state left unchanged",
                                   "a decay left out"])
def test_the_replayed_update_against_the_references_adamw(fault):
    """The step once more half-way up the warm-up moves every parameter as
    the reference's AdamW does; a state left unchanged reads 1, a decay left
    out reads over the limit on some leaf."""
    import test_lfm2_cell as lfm2_test
    model, config, t, m = _built(11)
    ref = harness.load_module("reference", CONFIG)
    feed = m["ring"][0]
    _, grads = model._trinity._replayed_first_step(m, feed)
    if fault == "state left unchanged":
        m["exe"] = lfm2_test._Swapped(m["exe"], run=lambda *a, **k: None)
    elif fault == "a decay left out":
        ref = lfm2_test._Swapped(
            ref, adamw=lambda p, steps, decay: ref._obj.adamw(p, steps, 0.0))
    got = model._xing._replayed_update(m, t, feed, grads, ref)
    limit = config["loss_tolerance"]["replayed_update_relative"]
    assert got["rate"] == pytest.approx(2e-4)
    if fault is None:
        assert got["worst"][0] <= limit and got["all"] <= limit / 10
    elif fault == "state left unchanged":
        assert got["all"] == 1.0 and got["worst"][0] == 1.0
    else:
        assert got["worst"][0] > limit, got


@pytest.mark.parametrize("reading, limit", [
    ("f32_loss", "relative"), ("f32_hidden", "hidden_relative"),
    ("gradient_attention", "first_gradient_attention_relative"),
    ("gradient_router", "first_gradient_router_relative"),
    ("gradient_experts_worst", "first_gradient_experts_worst_relative"),
    ("first_forward", "first_training_loss_relative"),
    ("update", "replayed_update_relative"), ("replay", "replay"),
])
def test_decide_names_the_limit_a_reading_exceeds(reading, limit):
    model = harness.load_module("models", CONFIG)
    tol = toy_sdar()[0]["loss_tolerance"]
    good = dict(f32_loss=0.0, f32_share=0.0, f32_hidden=0.0, first_hidden=0.0,
                update=0.0, first_loss=0.0, first_forward=0.0, replay=0.0,
                dropless=True, gradient_all=0.0, gradient_experts_worst=0.0,
                **{f"gradient_{k}": 0.0 for k in model.KINDS})
    assert model.decide(tol, good) == (True, [])
    for bad in (1.0, float("nan")):
        assert model.decide(tol, dict(good, **{reading: bad})) == \
            (False, [limit])
    assert model.decide(tol, dict(good, dropless=False)) == \
        (False, ["dropless"])


def test_a_kind_is_held_to_a_statistic_of_its_leaves():
    """Worst leaf for ``attention`` and ``rest``, median leaf for
    ``experts`` (and its worst besides), the smallest for ``router``:
    one router leaf that reads 2, as a layer whose mask rows flipped en
    bloc does in sound runs, moves none of the decided numbers but the
    experts' worst; every leaf raised, as a lower precision does, moves
    them all."""
    import jax.numpy as jnp
    model = harness.load_module("models", CONFIG)
    leaf = lambda v: jnp.full((4,), v, jnp.float32)  # noqa: E731
    block = lambda v: {k: leaf(v) for k in (  # noqa: E731
        model.ATTENTION_LEAVES + model.EXPERT_LEAVES + ("router_w",))}
    tree = lambda v, odd=None: {  # noqa: E731
        "wte": leaf(v), "final_norm_w": leaf(v), "head_w": leaf(v),
        "blocks": [dict(block(v), **(odd or {}) if i == 3 else {})
                   for i in range(6)]}
    ref = tree(1.0)

    def decided(got):
        off = model.gradient_difference(
            ref, [np.asarray(a) for a in
                  __import__("jax").tree_util.tree_leaves(got)])
        return {k: off[k][model.DECIDES[k]] for k in model.KINDS}, off

    flipped, off = decided(tree(1.01, {"router_w": leaf(3.0),
                                       "ln2_w": leaf(1.13)}))
    assert all(abs(v - 0.01) < 1e-6 for v in flipped.values()), flipped
    assert abs(off["router"][1] - 2.0) < 1e-6       # the worst router leaf
    assert abs(off["experts"][1] - 0.13) < 1e-6     # the guard's reading
    assert off["experts"][2] == "['blocks'][3]['ln2_w']"
    lower, _ = decided(tree(1.1))
    assert all(abs(v - 0.1) < 1e-6 for v in lower.values()), lower
    assert model.STATISTIC[model.DECIDES["router"]] == "smallest leaf"


def test_a_leaf_is_judged_with_its_kind():
    model = harness.load_module("models", CONFIG)
    kinds = {"['blocks'][0]['wq']": "attention",
             "['blocks'][5]['k_norm_w']": "attention",
             "['blocks'][1]['router_w']": "router",
             "['blocks'][1]['gate_w']": "experts",
             "['blocks'][1]['down_w']": "experts",
             "['blocks'][2]['ln2_w']": "experts",
             "['blocks'][2]['ln1_w']": "attention", "['wte']": "rest",
             "['head_w']": "rest", "['final_norm_w']": "rest"}
    for leaf, kind in kinds.items():
        assert model.kind_of(leaf) == kind, leaf


def test_the_lowered_step_names_the_ops_their_roles_and_the_tags():
    """What the readers and the by-op breakdown depend on: the flash ops
    under the ``attn`` tag and the ``block_diffusion`` scope in every role,
    the ``bd_stream`` tag, ``moe_ffn``'s parts, ``rope`` on the folded
    copies; NO ``Bias`` on a flash op and no tensor of the mask's size in
    the step; and the counters, read as DIFFERENCES: 2 layers' flash
    forward, forward again and backward under the mask form and nothing
    else through the flash kernels."""
    import jax.numpy as jnp
    from benchmark import part_scopes
    from benchmark.models import _train
    from paddle_tpu.ops import attention_ops
    config, traffic = toy_sdar()
    model = harness.load_module("models", CONFIG)
    form = dict(mask="block_diffusion", block="4")

    def now():
        pairs = attention_ops.FLASH_TILE_PAIRS_CTR
        return (attention_ops.FLASH_MASK_LOWERINGS_CTR.value(
                    kernel="fwd", **form),
                attention_ops.FLASH_MASK_LOWERINGS_CTR.value(
                    kernel="jax", **form),
                attention_ops.FLASH_MASK_LOWERINGS_CTR.value(mask="causal"),
                attention_ops.FLASH_LOWERINGS_CTR.value(
                    window="block_diffusion:4", kv_groups="8"),
                attention_ops.ROPE_LOWERINGS_CTR.value(width="16"),
                pairs.value(state="dead", **{"pass": "fwd"}, **form),
                pairs.value(state="masked", **{"pass": "fwd"}, **form),
                pairs.value(state="free", **{"pass": "fwd"}, **form))

    before = now()
    m = model.build_train(config, traffic, 11, 1, False)
    exe, scope = m["exe"], m["scope"]
    flash = [op for op in m["program"].global_block().ops
             if op.type.startswith("flash_attention")]
    assert len(flash) == 6 and not any(
        op.input("Bias") or op.input("X$Bias") for op in flash)
    assert all(op.attrs["block_diffusion"] == 4 and not op.attrs["causal"]
               for op in flash)
    feed = _train.put_ring(m["ring"], 1)[0]
    exe.run(m["program"], feed=feed, fetch_list=[m["loss"]], scope=scope)
    moved = tuple(b - a for a, b in zip(before, now()))
    assert moved[:5] == (4, 2, 0, 4, 12), moved
    # 80 rows in one tile of 80: one masked tile pair a lowering
    assert moved[5:] == (0, 4, 0), moved
    cb = next(p for p in exe._plans.values()
              if p.cb.fetch_names == (m["loss"],)).cb
    args = ([feed[n] for n in cb.feed_names],
            [scope.find_var(n) for n in cb.persist_ro],
            [scope.find_var(n) for n in cb.persist_rw], jnp.uint32(1))
    jaxpr = cb.jitted.trace(*args).jaxpr
    stacks = {s for s, _ in program_scopes_test._eqn_scopes(
        getattr(jaxpr, "jaxpr", jaxpr))}
    for op in ("pt.fwd/flash_attention/attn/block_diffusion",
               "pt.rc/flash_attention/attn/block_diffusion",
               "pt.bwd/flash_attention_grad/attn/block_diffusion",
               "pt.fwd/rope/attn", "pt.bwd/rope_grad/attn",
               "pt.fwd/concat/bd_stream", "pt.fwd/slice/bd_stream",
               "pt.fwd/reshape2/attn.bd_stream", "pt.fwd/mul/attn",
               "pt.fwd/rms_norm", "pt.opt/adamw", "pt.fwd/fused_lm_head_ce",
               "pt.fwd/lookup_table"):
        assert any(s == op or s.startswith(op + "/") for s in stacks), op
    for role_op in ("pt.fwd/moe_ffn/", "pt.bwd/moe_ffn_grad/",
                    "pt.rc/moe_ffn/"):
        seen = {part_scopes.part_of(s[len(role_op):], part_scopes.MOE_PARTS)
                for s in stacks if s.startswith(role_op)}
        assert seen >= set(part_scopes.MOE_PARTS), (role_op, seen)
    assert not any("/window" in s for s in stacks)
    # nothing of the mask's size: T = 80 rows a document's stream
    t = 2 * traffic["seq_len"]
    shapes = {tuple(v.aval.shape) for eqn in getattr(jaxpr, "jaxpr",
                                                     jaxpr).eqns
              for v in eqn.outvars if hasattr(v.aval, "shape")}
    assert not any(s[-2:] == (t, t) for s in shapes if len(s) >= 2)


def test_the_parent_commit_fails_at_once_on_the_new_cells_name(tmp_path):
    """``benchmark/run.py --workload <this cell>`` on the parent commit's
    files exits non-zero before anything is built: its ``BENCHMARK.json``
    has no such workload."""
    parent = "bd88094cb7f4564e07d671fa16686a7fb5c9e5b5"
    if subprocess.run(["git", "-C", ROOT, "cat-file", "-e",
                       parent + "^{commit}"]).returncode:
        pytest.skip("the parent commit is not in this checkout")
    tar = subprocess.run(["git", "-C", ROOT, "archive", parent,
                          "BENCHMARK.json", "benchmark"],
                         stdout=subprocess.PIPE, check=True).stdout
    subprocess.run(["tar", "-x", "-C", str(tmp_path)], input=tar, check=True)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "7", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=120)
    assert p.returncode != 0
    assert CELL in p.stderr and "not in BENCHMARK.json" in p.stderr
    assert not p.stdout.strip().startswith("{")
