"""CPU rehearsals of the on-chip benchmark (``benchmark/``) at toy widths.

They prove paths, arguments, control flow, the data-driven lookup and the
yardstick's arithmetic.  They produce no speed number: every value of a device
metric in here comes from the CPU and is thrown away.  Nothing in this file or
in what it imports loads libtpu.
"""

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

from benchmark import flops, harness, stats, trace_reduce  # noqa: E402
from benchmark.generators import decode_closed  # noqa: E402

SPEC = harness.load_spec()
#: the decode cell's entries, as a later PR appends them to BENCHMARK.json
#: (PERF.md says why they wait); rehearsed here beside the cells that are in
PENDING = harness.load_json("benchmark/pending/gpt1_decode_closed.json")
SPEC_ALL = dict(SPEC, **{k: SPEC[k] + PENDING[k] for k in
                         ("configs", "workloads", "end_to_end", "per_layer")})
BIG_SEED = 2 ** 31 + 12345          # the driver's seeds pass 32 signed bits


# -- toy sizes ------------------------------------------------------------------

def toy_gpt():
    c = copy.deepcopy(harness.load_json("benchmark/configs/gpt1.json"))
    c.update(n_embd=32, n_layer=2, n_head=4, n_positions=64, vocab_size=97)
    c["assumed"]["n_inner"] = 64
    t = copy.deepcopy(harness.load_json("benchmark/traffic/decode_closed.json"))
    t["engine"] = {"slots": 4, "max_seq": 64, "page_len": 8}
    t["table"] = [[5, 6], [12, 4], [3, 9], [7, 7], [20, 5], [4, 12]]
    t.update(start_fractions=[0.1, 0.6, 0.35, 0.85], reserve=2,
             open_after_completions=6, watch_slots=[0, 2], watch_limit=32)
    return c, t


def toy_bert(dp=False):
    c = copy.deepcopy(harness.load_json("benchmark/configs/bert_base.json"))
    c.update(hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
             intermediate_size=64, vocab_size=101, max_position_embeddings=32)
    # at these widths the fused head's chunked sum differs from the
    # reference's by 2e-4; the chip's tolerance is set at the real widths
    c["loss_tolerance"] = {"relative": 5e-3, "reason": "toy widths",
                           "first_training_loss_relative": 0.2}
    t = copy.deepcopy(harness.load_traffic(
        "mlm_s128%s" % ("_dp4" if dp else "")))
    t.update(batch_per_chip=4, seq_len=16, masked_per_seq=3, check_batch=2,
             ring=2, warmup_steps=1)
    if dp:
        t["chips_override"] = 4      # 4 of the 8 virtual CPU devices
    return c, t


def toy_resnet():
    c = copy.deepcopy(harness.load_json("benchmark/configs/resnet50.json"))
    c.update(image_size=64, num_classes=10)
    # bf16 AMP against float32 through batch-statistics BN over 8 images of
    # 2x2 pixels in the last stage: a few percent at this size (measured
    # 2.8 %; 1.6e-5 with AMP off), so the toy tolerance is wide
    c["loss_tolerance"] = {"relative": 0.2, "reason": "toy batch"}
    t = copy.deepcopy(harness.load_json("benchmark/traffic/imagenet_b256.json"))
    t.update(batch_per_chip=8, check_batch=8, ring=2, warmup_steps=1,
             learning_rate=0.01)
    return c, t


TOYS = {"gpt1_decode_closed": toy_gpt, "bert_base_mlm_s128": toy_bert,
        "bert_base_mlm_s128_dp4": lambda: toy_bert(dp=True),
        "resnet50_imagenet_b256": toy_resnet}


def check_contract_line(result, cell, trace):
    """The keys the driver reads, and only metrics this cell may report."""
    line = json.loads(json.dumps(result))          # it must serialise
    assert set(("correct", "attempted", "failed", "metrics", "device")) \
        <= set(line)
    assert set(("platform", "kind", "count", "memory_peak_bytes")) \
        <= set(line["device"])
    section = "per_layer" if trace else "end_to_end"
    allowed = {m["name"]: m["unit"]
               for m in harness.metrics_of_cell(SPEC_ALL, section, cell)}
    for name, m in line["metrics"].items():
        assert name in allowed, f"{name} is not a {section} metric of {cell}"
        assert m["unit"] == allowed[name]
        assert isinstance(m["value"], float) and np.isfinite(m["value"])
    if not trace:
        assert set(line["metrics"]) == set(allowed)
        assert line["metrics"]["setup_s"]["value"] > 0
    else:
        assert line["metrics"], "a traced run reports a per-layer metric"
    return line


# -- each traffic kind end to end, to the contract's last line ------------------

@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", sorted(TOYS))
def test_cell_end_to_end_on_cpu(cell, trace):
    config, traffic = TOYS[cell]()
    result = harness.run_cell(cell, seed=BIG_SEED, seconds=1.0,
                              trace=bool(trace), on_chip=False,
                              config=config, traffic=traffic, spec=SPEC_ALL)
    line = check_contract_line(result, cell, trace)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"     # and so: not a result
    # what run.py repeats on standard error: the comparisons, then the verdict
    assert len(line["compared"]) >= 2
    assert line["compared"][-1].startswith("correct: ")


def test_decode_traced_run_reads_the_schedulers_counts():
    config, traffic = toy_gpt()
    r = harness.run_cell("gpt1_decode_closed", seed=7, seconds=1.0,
                         trace=True, on_chip=False, config=config,
                         traffic=traffic, spec=SPEC_ALL)
    m = {k: v["value"] for k, v in r["metrics"].items()}
    assert 90.0 <= m["slot_occupancy_mean"] <= 100.0
    # the table's own share: sum(P - 1) / sum(P + A - 1) = 45 / 88
    assert abs(m["prefill_iter_share"] - 100 * 45 / 88) < 6.0
    assert 0 < m["kv_pages_peak_share"] <= 100.0
    assert m["decode_iter_ms_p50"] > 0 and m["queue_wait_p50_ms"] >= 0


# -- run.py -------------------------------------------------------------------------

def test_run_py_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "bert_base_mlm_s128", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())


def test_run_py_refuses_an_unknown_workload():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "no_such_cell"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "no_such_cell" in p.stderr


# -- BENCHMARK.json against the files it names --------------------------------------

@pytest.mark.parametrize("spec", [SPEC, SPEC_ALL], ids=["as_listed",
                                                      "with_pending"])
def test_every_entry_of_benchmark_json_finds_its_files(spec):
    for c in spec["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        for kind in ("models", "reference"):
            assert os.path.exists(os.path.join(
                ROOT, "benchmark", kind, c["name"] + ".py"))
        body = harness.load_json(c["file"])
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        assert body["source"] == c["source"]
    for w in spec["workloads"]:
        t = harness.load_traffic(w["traffic"])
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "generators", t["kind"] + ".py"))
        assert len(w["why"]) <= 200
    for m in spec["per_layer"]:
        mod = harness.load_module("layer_metrics", m["name"])
        assert callable(mod.read)
        moved = harness.find(spec["end_to_end"], m["moves"], "metric")
        cells = m.get("workloads") or [w["name"] for w in spec["workloads"]]
        for cell in cells:                 # reported wherever this one is
            assert "workloads" not in moved or cell in moved["workloads"]
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(
        1, len(spec["workloads"]) // 4)




def test_pending_entries_only_append():
    for k in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC_ALL[k]]
        assert len(names) == len(set(names))
    listed = {w["name"] for w in SPEC["workloads"]}
    for m in PENDING["end_to_end"] + PENDING["per_layer"]:
        assert not listed & set(m["workloads"])


@pytest.mark.parametrize("metric",
                         [m["name"] for m in SPEC_ALL["per_layer"]])
def test_reader_with_nothing_to_read_returns_nothing(metric):
    empty = {"spans": [], "counters": {"generated_tokens": 0,
                                       "kv_pages_peak": 0},
             "facts": {"slots": 4, "batch": 8, "chips": 1,
                       "flops_per_sample": 1.0, "samples_per_s": 1.0},
             "e2e": {}, "trace": None, "trace_window": None,
             "config": {"image_size": 224}, "traffic": {}, "peaks": None,
             "chips": 1}
    assert harness.load_module("layer_metrics", metric).read(empty) is None


# -- the decode traffic: same multiset and same schedule under every seed -----------

TRAFFIC = harness.load_json("benchmark/traffic/decode_closed.json")


def _first(it, n):
    return [next(it) for _ in range(n)]


def test_every_round_is_the_table_in_table_order():
    n = 3 * len(TRAFFIC["table"])
    got = _first(decode_closed.rounds(TRAFFIC), n)
    assert got == 3 * [tuple(p) for p in TRAFFIC["table"]]


def test_length_table_is_as_the_traffic_file_says():
    table = TRAFFIC["table"]
    assert len(table) == 32 == TRAFFIC["engine"]["slots"]
    assert len(TRAFFIC["start_fractions"]) == 32
    assert all(8 <= p <= 256 and 16 <= a <= 128 for p, a in table)
    assert all(p + a <= TRAFFIC["engine"]["max_seq"] for p, a in table)
    assert sorted(round(f * 32 - 0.5) for f in TRAFFIC["start_fractions"]) \
        == list(range(32))                  # stratified phases


@pytest.mark.parametrize("prompt,answer,fraction,want", [
    (10, 5, 0.0, (10, 5)),       # nothing ridden yet: the whole request
    (10, 5, 0.5, (3, 5)),        # 14 iterations, 7 left: 3 prompt + 5 - 1
    (10, 5, 0.9, (1, 2)),        # inside the answer
    (190, 35, 0.3594, (110, 35)),
])
def test_cut_to_phase(prompt, answer, fraction, want):
    got = decode_closed.cut_to_phase(prompt, answer, fraction)
    assert got == want
    assert got[0] + got[1] - 1 <= prompt + answer - 1


def test_simulated_schedule_never_empties_the_reserve():
    sim = decode_closed.simulate(TRAFFIC, 1500)
    per_iter = {}
    for r in sim["requests"]:
        if r["done"] is not None:
            per_iter[r["done"]] = per_iter.get(r["done"], 0) + 1
    assert max(per_iter.values()) <= TRAFFIC["reserve"]
    gen = np.array(sim["generated"][200:1400])
    # steady from the start: output tokens per iteration near the table's share
    share = sum(a for _, a in TRAFFIC["table"]) / sum(
        p + a - 1 for p, a in TRAFFIC["table"])
    assert abs(gen.mean() / 32 - share) < 0.02


class _FakeCache:
    """``pages_in_use`` is the last thing the dispatcher's poll calls, so the
    fake engine reads it as "a poll has ended"."""

    def __init__(self):
        import threading
        self.n_pages = 64
        self.polled = threading.Event()

    def pages_in_use(self):
        self.polled.set()
        return 0

    def pages_of(self, slot):
        return []

    def buffers_alive(self):
        return True


class _FakeEngine:
    """The engine's interface, recording what the scheduler feeds it.  An
    iteration ends only after two polls of the dispatcher have ended since it
    began (so one whole poll ran after the last iteration's completions
    resolved): as on the chip, where an iteration is twenty polls long, but
    with no dependence on how fast this machine is."""

    def __init__(self, slots, vocab=50):
        self.max_slots, self.page_len, self.max_seq = slots, 8, 64
        self.max_pages, self.vocab = 8, vocab
        self.cache = _FakeCache()
        self.trace_count = 1
        self.log = []

    def reserve_slot(self, slot, n_pages):
        return True

    def ensure_page(self, slot, pos):
        return True

    def release_slot(self, slot):
        pass

    def run_iteration(self, ids, pos, active):
        for _ in range(2):
            self.cache.polled.clear()
            self.cache.polled.wait(0.5)    # the dispatcher stops at the close
        self.log.append((tuple(int(p) for p in pos),
                         tuple(bool(a) for a in active)))
        out = np.zeros((self.max_slots, self.vocab), np.float32)
        out[np.arange(self.max_slots), (np.asarray(ids) + 1) % self.vocab] = 1
        return out


def _dispatch_with_fake_engine(seed):
    from paddle_tpu import serving
    _, traffic = toy_gpt()
    eng = _FakeEngine(traffic["engine"]["slots"])

    class Ctx:
        pass
    ctx = Ctx()
    ctx.traffic, ctx.config, ctx.seed = traffic, {}, seed
    ctx.seconds, ctx.trace, ctx.on_chip = 1.0, False, False
    ctx.trace_seconds = 0.0
    ctx.clock = harness.SetupClock(0.0)
    ctx.meter = type("M", (), {"compiles": 0})()
    ctx.spans = harness.SpanWindow()
    ctx.reference = None
    ctx.model = type("Model", (), {
        "build_server": staticmethod(lambda c, t, s, oc: {
            "engine": eng, "server": serving.DecodeServer(eng),
            "vocab": eng.vocab}),
        "check_logits": staticmethod(lambda c, b, rows, ref: {
            "ok": True, "detail": "fake engine"})})
    out = decode_closed.run(ctx)
    return eng.log, out


@pytest.mark.parametrize("seeds", [(11, 11), (11, BIG_SEED)])
def test_dispatcher_schedule_is_a_function_of_the_list(seeds):
    """Twice through the real DecodeServer and scheduler over a fake engine,
    under the same seed and under two seeds (which draw other token ids): the
    same positions in the same slots at every iteration (so the same
    admission order and the same output tokens per iteration), and the
    schedule the iteration-space simulation predicts."""
    log_a, out_a = _dispatch_with_fake_engine(seeds[0])
    log_b, out_b = _dispatch_with_fake_engine(seeds[1])
    n = min(len(log_a), len(log_b))
    assert n > 40                       # several rounds of the toy table
    assert log_a[:n] == log_b[:n]
    assert out_a["correct"] and out_b["correct"]
    _, traffic = toy_gpt()
    sim = decode_closed.simulate(traffic, n)
    slots = traffic["engine"]["slots"]
    started = [sum(1 for s in range(slots) if a[s] and p[s] == 0)
               for p, a in log_a[:n]]
    sim_started = [0] * n
    for it, _, _, _ in sim["admissions"]:
        sim_started[it] += 1
    assert started == sim_started


# -- what the review of PR 23 asked to be pinned ------------------------------------

def test_traced_training_run_records_a_step_even_past_the_deadline():
    """A step that runs from before the trace's start to after the deadline
    used to leave the profiler unstarted and ``stop()`` raising; the window
    is now one step longer instead."""
    config, traffic = toy_bert()
    r = harness.run_cell("bert_base_mlm_s128", seed=3, seconds=0.001,
                         trace=True, on_chip=False, config=config,
                         traffic=traffic)
    assert r["correct"] is True and r["attempted"] >= 1
    assert "step_device_ms.train" in r["metrics"] or r["metrics"]


def test_same_as_traffic_is_the_other_mix_under_a_second_name():
    a, b = harness.load_traffic("mlm_s128"), harness.load_traffic(
        "mlm_s128_dp4")
    assert b["same_as"] == "mlm_s128"
    for k, v in a.items():
        assert b[k] == v


@pytest.mark.parametrize("pool,weights,ok", [
    ("float32", "float32", True), ("bfloat16", "float32", False),
    ("float32", "bfloat16", False)])
def test_decode_check_reads_what_the_engine_holds_by_dtype(pool, weights, ok):
    """At the TPU's default matmul precision a bfloat16 KV pool gives the
    logits of a float32 one, so the stated dtypes are compared as dtypes."""
    import jax.numpy as jnp
    from benchmark.models import gpt1

    class Engine:
        page_len = 8
        cache = type("C", (), {"k": jnp.zeros(2, pool),
                               "v": jnp.zeros(2, pool)})()
        params = {"w": jnp.zeros(2, weights), "b": jnp.zeros(2, weights)}
    held = gpt1.served_dtypes(Engine)
    assert held == {"kv_pool": [pool], "weights": [weights]}
    stated = harness.load_json("benchmark/configs/gpt1.json")["served_dtypes"]
    assert all(held[k] == [stated[k]] for k in held) is ok


# -- the trace reduction, on a small recorded trace ---------------------------------

def _ev(plane, name, start, dur, line="XLA Ops"):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur}


def test_interval_arithmetic():
    u = trace_reduce.union([(5, 9), (0, 3), (2, 4), (9, 9)])
    assert u == [(0, 4), (5, 9)] and trace_reduce.total(u) == 8
    assert trace_reduce.subtract([(0, 10)], u) == [(4, 5), (9, 10)]
    assert trace_reduce.clip(u, (3, 6)) == [(3, 4), (5, 6)]
    assert trace_reduce.op_class("%fusion.123") == "fusion"
    assert trace_reduce.op_class("all-reduce-start.2") == "all-reduce-start"
    assert trace_reduce.op_class("conv1x1_stats_nchw.7") == "conv1x1_stats_nchw"
    assert trace_reduce.is_collective("all-reduce-done.1")
    assert not trace_reduce.is_collective("fusion.3")


def test_reduction_of_a_hand_made_trace():
    d0, d1 = "/device:TPU:0", "/device:TPU:1"
    events = [
        _ev(d0, "fusion.1", 0, 400), _ev(d0, "copy.2", 300, 300),
        _ev(d0, "all-reduce.3", 700, 200), _ev(d0, "fusion.4", 800, 50),
        _ev(d1, "fusion.1", 100, 500), _ev(d1, "all-reduce.3", 600, 300),
        _ev("/host:CPU", "bench_mark", 0, 0, line="python"),
    ]
    events[-1]["t_perf"] = 10.0
    red = trace_reduce.reduce_events(events, (0, 1000))
    assert red["n_devices"] == 2 and red["window_s"] == pytest.approx(1e-6)
    assert red["devices"][d0]["busy_s"] == pytest.approx(800e-9)
    assert red["devices"][d1]["busy_s"] == pytest.approx(800e-9)
    assert red["busy_s"] == pytest.approx(800e-9)
    assert red["idle_share"] == pytest.approx(0.2)
    # d0: the all-reduce runs 700-900, compute covers 800-850 -> 150 exposed;
    # d1: 600-900 with no compute -> 300 exposed; the worst chip counts
    assert red["devices"][d0]["collective_exposed_s"] == pytest.approx(150e-9)
    assert red["collective_exposed_s"] == pytest.approx(300e-9)
    assert red["devices"][d0]["gaps"] == [(600, 700), (900, 1000)]
    assert red["ops"]["fusion"] == pytest.approx((450 + 500) / 2 * 1e-9)
    assert trace_reduce.clock_offset_ns(events) == pytest.approx(-10e9)
    gaps = trace_reduce.attribute_gaps(
        red["devices"][d0]["gaps"],
        [("serving.decode_iter", 0, 650), ("inner", 610, 640)])
    assert gaps == {"inner": pytest.approx(30e-9),
                    "serving.decode_iter": pytest.approx(20e-9),
                    "between_spans": pytest.approx(150e-9)}


FIXTURES = sorted(f for f in os.listdir(os.path.join(
    ROOT, "benchmark", "fixtures")) if f.endswith(".json"))


@pytest.mark.parametrize("name", FIXTURES)
def test_reduction_of_a_recorded_trace(name):
    """A slice of a real v5e trace (recorded by this benchmark, PR 23): the
    reduction's numbers are checked against values worked out by a second,
    brute-force computation over a nanosecond grid."""
    with open(os.path.join(ROOT, "benchmark", "fixtures", name)) as f:
        fx = json.load(f)
    events = fx["events"]
    red = trace_reduce.reduce_events(events)
    assert red["n_devices"] == fx["expect"]["n_devices"]
    dev = [e for e in events if e["name"] != trace_reduce.MARK]
    w0 = min(e["start_ns"] for e in dev)
    w1 = max(e["start_ns"] + e["dur_ns"] for e in dev)
    first = sorted(red["devices"])[0]
    edges = sorted({w0, w1} | {e["start_ns"] for e in dev}
                   | {e["start_ns"] + e["dur_ns"] for e in dev})
    busy = 0
    for a, b in zip(edges, edges[1:]):
        if any(e["plane"] == first and e["start_ns"] <= a
               and e["start_ns"] + e["dur_ns"] >= b for e in dev):
            busy += b - a
    assert red["devices"][first]["busy_s"] == pytest.approx(busy / 1e9)
    assert red["busy_s"] == pytest.approx(fx["expect"]["busy_s"], rel=1e-9)
    assert red["idle_share"] == pytest.approx(fx["expect"]["idle_share"],
                                              rel=1e-9)
    top = trace_reduce.top(red["ops"], 3)
    assert [k for k, _ in top] == fx["expect"]["top_ops"]


# -- peaks, FLOPs and bytes against hand-worked values ------------------------------

def test_peaks_table():
    p = flops.load_peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.load_peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        flops.load_peaks("source")


@pytest.mark.parametrize("what,got,want", [
    # 6*12*(4*768^2 + 2*768*3072)*128 + 6*30522*768*20 + 12*12*768*128*128
    ("bert", flops.bert_mlm_train_flops_per_sample(768, 12, 3072, 30522, 128,
                                                   20),
     65229815808 + 2812907520 + 1811939328),
    # the textbook 4.09 GMAC of ResNet-50 v1.5 at 224 (torchvision: 4.09
    # GFLOPs counted as MACs), + fc
    ("resnet50_forward", flops.resnet50_forward_flops_per_sample(), 8178368512),
    ("resnet50_train", flops.resnet50_train_flops_per_sample(),
     3 * 8178368512),
    # one token at context 100: 2*12*(4*768^2+2*768*3072) + 2*40478*768
    # + 4*12*768*100
    ("gpt_decode", flops.gpt_decode_flops_per_token(768, 12, 3072, 40478, 100),
     169869312 + 62174208 + 3686400),
])
def test_flop_counts(what, got, want):
    assert got == pytest.approx(want, rel=1e-12)


def test_resnet50_sites():
    sites = flops.resnet50_conv_sites()
    assert len(sites) == 53                      # 1 stem + 16*3 + 4 shortcuts
    # b0 and b2 of all 16 blocks and the 4 projection shortcuts
    assert sum(s["k"] == 1 for s in sites) == 36
    by = {s["name"]: s for s in sites}
    assert by["stem"] == dict(name="stem", cin=3, cout=64, k=7, stride=2,
                              hout=112)
    assert by["res1_0.b1"]["stride"] == 2 and by["res1_0.b1"]["hout"] == 28
    assert by["res3_2.b2"] == dict(name="res3_2.b2", cin=512, cout=2048, k=1,
                                   stride=1, hout=7)
    # stem MACs: 64*112*112*3*49 = 118013952
    assert 64 * 112 * 112 * 3 * 49 == 118013952


@pytest.mark.parametrize("fl,by,bound", [
    # a 1x1 convolution [256, 64, 3136] -> [256, 64, 3136] in bf16 with its
    # weight: 32 flops per byte, under the chip's 240
    (2 * 256 * 3136 * 64 * 64,
     256 * 3136 * 64 * 2 + 64 * 64 * 2 + 256 * 3136 * 64 * 2, "memory"),
    (1e15, 1.0, "compute"),
])
def test_roofline_seconds_is_the_larger_of_the_two_bounds(fl, by, bound):
    t, got = flops.roofline_seconds(fl, by, flops.load_peaks("TPU v5 lite"))
    assert got == bound
    assert t == pytest.approx(by / 819e9 if bound == "memory"
                              else fl / 197e12)


def test_percentile_and_spread():
    v = list(range(1, 101))
    assert stats.percentile(v, 90) == 90 and stats.percentile(v, 50) == 50
    assert stats.percentile([3.0], 90) == 3.0
    assert stats.percentile([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11], 90) == 10
    assert stats.iqr_share([10, 10, 10, 10, 10, 10]) == 0
    assert stats.iqr_share([9, 10, 10, 10, 10, 11]) == pytest.approx(
        (10.25 - 9.75) / 10)
