"""What PR 24 put inside the program for the benchmark to read, rehearsed on
the CPU at toy widths: the ``pt.<role>/<op type>`` scopes of the compiled
step and the ``pt.decode/*`` scopes of the decode step, the first call's
compile phases, and the decode iteration's host phases and per-token times.
Nothing here is a speed number."""

import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_benchmark_rehearsal as rehearsal  # noqa: E402


# -- scopes in the lowered step -------------------------------------------------

def _eqn_scopes(jaxpr, prefix=""):
    """(full name stack, primitive) of every equation, sub-jaxprs included
    (their name stacks continue their parent's)."""
    import jax
    for eqn in jaxpr.eqns:
        stack = prefix + str(eqn.source_info.name_stack)
        yield stack, eqn.primitive.name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqn_scopes(sub, stack + "/")


def _train_step_scopes(cell):
    """One step of the toy ``cell`` through the executor; returns the
    optimised program's ops, the traced step's equations with their name
    stacks, and the executor."""
    import jax.numpy as jnp
    from benchmark.models import _train
    config, traffic = rehearsal.TOYS[cell]()
    model = harness.load_module("models", harness.find(
        harness.load_spec()["workloads"], cell, "workload")["config"])
    m = model.build_train(config, traffic, 11, 1, False)
    exe, scope = m["exe"], m["scope"]
    feed = _train.put_ring(m["ring"], 1)[0]
    exe.run(m["program"], feed=feed, fetch_list=[m["loss"]], scope=scope)
    plan = next(p for p in exe._plans.values()
                if p.cb.fetch_names == (m["loss"],))
    cb = plan.cb
    args = ([feed[n] for n in cb.feed_names],
            [scope.find_var(n) for n in cb.persist_ro],
            [scope.find_var(n) for n in cb.persist_rw], jnp.uint32(1))
    jaxpr = cb.jitted.trace(*args).jaxpr
    return (plan.program.global_block().ops,
            list(_eqn_scopes(getattr(jaxpr, "jaxpr", jaxpr))), exe)


@pytest.mark.parametrize("cell", ["bert_base_mlm_s128",
                                  "resnet50_imagenet_b256"])
def test_every_op_of_the_step_lowers_under_its_program_scope(cell):
    from paddle_tpu.framework.executor import op_scope
    ops, eqns, exe = _train_step_scopes(cell)
    want = {op_scope(op) for op in ops if op.type not in ("feed", "fetch")}
    assert {w.split("/")[0] for w in want} >= {"pt.fwd", "pt.bwd", "pt.opt"}
    stacks = {s for s, _ in eqns}
    missing = {w for w in want
               if not any(s == w or s.startswith(w + "/") for s in stacks)}
    # an op may lower to nothing (a reshape of a constant, an alias); what
    # must hold is that the kinds the metrics read are all there
    assert not {m for m in missing if m.split("/")[1].startswith(
        ("fused_", "flash_", "dropout", "layer_norm", "mul", "adam",
         "momentum", "conv2d", "batch_norm"))}, missing
    assert len(missing) <= len(want) // 10, missing
    # JAX's partial evaluation hoists loop-invariant casts out of a scanned
    # body (the fused head's ``w.astype``) with the body's relative name
    # stack, i.e. with none: a handful of cheap equations, nothing else
    outside = [(s, p) for s, p in eqns if not s.startswith("pt.")]
    assert {p for _, p in outside} <= {"convert_element_type",
                                       "broadcast_in_dim", "jit"}, outside
    assert len(outside) <= len(eqns) // 100, outside
    assert exe.dispatch_stats()["traces"] == 2       # startup + the step


def test_a_generic_grad_keeps_the_grad_ops_scope():
    """Forward work the generic vjp lowers again inside the backward reads
    ``pt.bwd/<type>_grad/jvp(...)``, its transpose ``.../transpose(jvp(``:
    that is what separates repeated forward work from the backward."""
    _, eqns, _ = _train_step_scopes("bert_base_mlm_s128")
    bwd = [s for s, _ in eqns if s.startswith("pt.bwd/")]
    assert any("/jvp(" in s and "transpose(" not in s for s in bwd)
    assert any("transpose(jvp(" in s for s in bwd)
    assert not any(s.startswith("pt.fwd/") and "_grad" in s.split("/")[1]
                   for s, _ in eqns)


DECODE_PARTS = {"embed", "qkv", "kv_write", "kv_gather", "attention",
                "attn_out", "ffn", "lm_head"}


def _toy_engine(slots=2):
    from benchmark.models import gpt1
    config, traffic = rehearsal.toy_gpt()
    traffic["engine"]["slots"] = slots
    return gpt1.build_server(config, traffic, 5, False)


def test_the_decode_step_carries_its_eight_scopes():
    import jax.numpy as jnp
    built = _toy_engine()
    eng = built["engine"]
    S = eng.max_slots
    eng.run_iteration(np.ones(S, np.int32), np.zeros(S, np.int32),
                      np.ones(S, bool))
    assert eng.trace_count == 1
    # the step's own jaxpr, from the jit's cache: nothing is traced again
    jaxpr = eng.model._step.trace(
        eng.params, eng.cache.k, eng.cache.v, jnp.ones(S, jnp.int32),
        jnp.zeros(S, jnp.int32), jnp.asarray(eng.page_table),
        jnp.ones(S, bool)).jaxpr
    eqns = list(_eqn_scopes(getattr(jaxpr, "jaxpr", jaxpr)))
    parts = {s.split("/")[1] for s, _ in eqns if s.startswith("pt.decode/")}
    assert parts == DECODE_PARTS
    assert not [(s, p) for s, p in eqns if not s.startswith("pt.decode/")]
    eng.run_iteration(np.ones(S, np.int32), np.ones(S, np.int32),
                      np.ones(S, bool))
    assert eng.trace_count == 1


# -- the first call's phases ----------------------------------------------------

def _phase_sums(block):
    from paddle_tpu import monitor
    fam = monitor.REGISTRY.get("paddle_tpu_compile_phase_seconds")
    return {lb["phase"]: c.snapshot()[1] for lb, c in fam.series()
            if lb["block"] == block}


def test_a_compile_leaves_its_phases_as_spans_and_in_the_histogram():
    import paddle_tpu as pt
    from paddle_tpu import layers, monitor
    from paddle_tpu.framework import (Program, Scope, program_guard,
                                      scope_guard)
    before = _phase_sums("train") if monitor.REGISTRY.get(
        "paddle_tpu_compile_phase_seconds") else {}
    scope, main, startup = Scope(), Program(), Program()
    with scope_guard(scope), program_guard(main, startup):
        x = layers.data("x", shape=[6], dtype="float32")
        loss = layers.mean(layers.fc(x, size=3))
        pt.optimizer.SGD(0.1).minimize(loss)
        exe = pt.Executor()
        exe.run(startup, scope=scope)
        monitor.TRACER.clear()
        feed = {"x": np.ones((4, 6), np.float32)}
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    evs = [e for e in monitor.TRACER.chrome_events() if e.get("ph") == "X"]
    whole = [e for e in evs if e["name"] == "xla.compile"]
    assert len(whole) == 1                      # later steps compile nothing
    t0, t1 = whole[0]["ts"], whole[0]["ts"] + whole[0]["dur"]
    inside = {}
    for e in evs:
        if e["name"].startswith("compile."):
            inside[e["name"][len("compile."):]] = e
    assert set(inside) == {"prepare", "trace", "lower", "backend",
                           "first_run"}
    assert inside["prepare"]["ts"] + inside["prepare"]["dur"] <= t0 + 1
    parts = [inside[p] for p in ("trace", "lower", "backend", "first_run")]
    for e in parts:
        assert t0 - 1 <= e["ts"] and e["ts"] + e["dur"] <= t1 + 1   # us
    assert sum(e["dur"] for e in parts) == pytest.approx(whole[0]["dur"],
                                                         abs=5)
    assert inside["trace"]["dur"] > 0 and inside["backend"]["dur"] > 0
    assert "persist_cache" in inside["backend"]["args"]
    after = _phase_sums("train")
    for phase, e in inside.items():
        assert after[phase] - before.get(phase, 0.0) == pytest.approx(
            e["dur"] / 1e6, abs=2e-5)
    assert after.get("retrace", 0.0) == before.get("retrace", 0.0)
    assert exe.dispatch_stats()["traces"] == 2


# -- the decode iteration's host phases and the tokens' times -------------------

def _spans(name_prefix):
    from paddle_tpu import monitor
    return [e for e in monitor.TRACER.chrome_events()
            if e.get("ph") == "X" and e["name"].startswith(name_prefix)]


def test_decode_iteration_phases_nest_and_share_the_iteration():
    from paddle_tpu import monitor
    built = _toy_engine(slots=2)
    server, vocab = built["server"], built["vocab"]
    monitor.TRACER.clear()
    server.start()
    rng = np.random.RandomState(0)
    futs = [server.submit("t", rng.randint(1, vocab, size=n).astype(
        np.int64), max_new_tokens=a, eos_id=None)
        for n, a in ((5, 4), (3, 6), (7, 3))]
    outs = [f.result(timeout=120) for f in futs]
    server.stop()
    assert [len(o) for o in outs] == [4, 6, 3]
    iters = {e["args"]["iter"]: e for e in _spans("serving.decode_iter")
             if e["name"] == "serving.decode_iter"}
    assert len(iters) >= 9
    kids = {}
    for e in _spans("serving.decode_step.") + _spans(
            "serving.decode_iter.sample"):
        kids.setdefault(e["args"]["iter"], {})[e["name"].rsplit(
            ".", 1)[1]] = e
    assert set(kids) == set(iters)
    for n, it in iters.items():
        k = kids[n]
        assert set(k) == {"dispatch", "device_wait", "logits_to_host",
                          "sample"}
        a, b = it["ts"], it["ts"] + it["dur"]
        inner = [k[p] for p in ("dispatch", "device_wait",
                                "logits_to_host")]
        assert a - 1 <= inner[0]["ts"]
        for x, y in zip(inner, inner[1:]):           # back to back, in order
            assert x["ts"] + x["dur"] == pytest.approx(y["ts"], abs=1)
        assert inner[-1]["ts"] + inner[-1]["dur"] <= b + 1
        assert k["sample"]["ts"] == pytest.approx(b, abs=1)   # follows it
        # the three phases and what surrounds them make up the iteration
        assert sum(e["dur"] for e in inner) <= it["dur"] + 2
        assert sum(e["dur"] for e in inner) >= 0.5 * it["dur"]


def test_every_requests_token_times_ride_its_decode_span():
    _, out = rehearsal._dispatch_with_fake_engine(11)
    decode = [s for s in out["spans"] if s[0] == "serving.decode"]
    assert len(decode) > 10
    for _, t0, t1, args in decode:
        times = args["token_ms"]
        assert len(times) == args["generated"] > 0
        assert all(b >= a for a, b in zip(times, times[1:]))
        assert args["ttft_ms"] == times[0] > 0
        # the last token is made where the decode phase ends
        assert times[-1] == pytest.approx(
            (t1 - t0) * 1e3 + args_offset(out, args, t0), abs=50.0)


def args_offset(out, args, t_slot):
    """token_ms counts from submission, the decode span from slot
    admission: the difference is the request's admit + queue_wait."""
    mine = [s for s in out["spans"] if s[3].get("trace") == args["trace"]
            and s[0] in ("serving.admit", "serving.queue_wait")]
    return sum((s[2] - s[1]) * 1e3 for s in mine)
