"""GSPMD model parallelism (parallel.partitioner + with_gspmd): logical
axis inference, planner-driven rule-table selection against
FLAGS_memory_budget_mb, sharded-vs-single-chip loss parity, ZeRO-1
composition, partition-fingerprint refusal (naming both rule tables),
sharded-snapshot restore, and the per-device HBM attribution."""

import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu import optimizer as opt
from paddle_tpu.framework import Executor, Program, program_guard
from paddle_tpu.framework.scope import Scope, global_scope, scope_guard
from paddle_tpu.parallel import (LogicalAxisRules, choose_rules,
                                 infer_logical_axes, make_topology_mesh,
                                 mesh_axis_sizes, partition_program,
                                 rule_table)
from paddle_tpu.parallel.partitioner import partition_fingerprint


def _build_mlp(prefix="gs"):
    x = layers.data("x", shape=[8], dtype="float32")
    y = layers.data("y", shape=[1], dtype="int64")
    h = layers.fc(x, size=16, act="relu", name=f"{prefix}_fc1")
    pred = layers.fc(h, size=4, act="softmax", name=f"{prefix}_fc2")
    loss = layers.mean(layers.cross_entropy(pred, y))
    opt.AdamOptimizer(learning_rate=0.01).minimize(loss)
    return loss


def _train_mlp(compiled_fn, steps=4, prefix="gs"):
    main, start = Program(), Program()
    with program_guard(main, start), scope_guard(Scope()):
        loss = _build_mlp(prefix)
        main.random_seed = 7
        start.random_seed = 7
        compiled = compiled_fn(main, loss)
        exe = Executor()
        exe.run(pt.default_startup_program(), seed=99)
        rng = np.random.RandomState(3)
        out = []
        for _ in range(steps):
            xv = rng.rand(16, 8).astype(np.float32)
            yv = rng.randint(0, 4, (16, 1)).astype(np.int64)
            lv, = exe.run(compiled, feed={"x": xv, "y": yv},
                          fetch_list=[loss.name])
            out.append(float(np.asarray(lv)))
        scope = global_scope()
        moment = next(
            (scope.find_var(n) for n in scope.local_var_names()
             if "moment1" in n and f"{prefix}_fc1.w" in n), None)
        return out, main, moment


# ---------------------------------------------------------------------------
# topology mesh
# ---------------------------------------------------------------------------

def test_make_topology_mesh_and_axis_sizes():
    mesh = make_topology_mesh({"dp": 2, "mp": 4})
    assert mesh.axis_names == ("dp", "mp")
    assert mesh_axis_sizes(mesh) == {"dp": 2, "mp": 4}
    with pytest.raises(ValueError, match="devices"):
        make_topology_mesh({"dp": 3, "mp": 5})


# ---------------------------------------------------------------------------
# logical-axis inference
# ---------------------------------------------------------------------------

def test_infer_logical_axes_transformer():
    """The op-graph walk derives the Megatron layout the hand-written
    ``annotate_tensor_parallel`` encodes by name suffix: embeddings
    (vocab, embed), fused qkv column-parallel, the CE-feeding head
    weight relabelled onto the vocab axis."""
    from paddle_tpu.models import transformer as T
    main, start = Program(), Program()
    with program_guard(main, start), scope_guard(Scope()):
        cfg = T.BertConfig(vocab_size=64, d_model=16, n_layer=1, n_head=4,
                           d_inner=32, max_pos=32, dropout=0.0)
        _, _, loss = T.build_bert_pretrain(cfg, seq_len=8)
        opt.SGDOptimizer(learning_rate=0.1).minimize(loss)
        axes = infer_logical_axes(main)
    assert axes["word_embedding"] == ("vocab", "embed")
    assert axes["enc_0.attn.qkv.w"][0] == "embed"      # column-parallel
    assert axes["enc_0.attn.qkv.w"][1] in ("mlp", "heads")
    assert axes["enc_0.ffn.fc1.w"] == ("embed", "mlp")
    # the matmul feeding cross_entropy projects onto the vocabulary
    assert axes["mlm_out.w"][1] == "vocab"
    assert axes["mlm_out.b"] == ("vocab",)


def test_apply_rules_divisibility_guard():
    """A dim the mesh axis can't divide stays replicated instead of
    producing a ragged shard the scope layout can't hold."""
    main, start = Program(), Program()
    with program_guard(main, start), scope_guard(Scope()):
        x = layers.data("x", shape=[8], dtype="float32")
        h = layers.fc(x, size=6, act="relu", name="rag_fc")  # 6 % 4 != 0
        loss = layers.mean(h)
        opt.SGDOptimizer(learning_rate=0.1).minimize(loss)
        stamp = partition_program(main, {"dp": 2, "mp": 4},
                                  rules="mp_hidden")
        w = next(n for n in stamp.get("params", {}) if "rag_fc.w" in n) \
            if stamp["params"] else None
    assert w is None, f"6-wide fc must stay replicated, got {w}"


# ---------------------------------------------------------------------------
# planner-driven selection
# ---------------------------------------------------------------------------

def _planner_program():
    main, start = Program(), Program()
    with program_guard(main, start), scope_guard(Scope()):
        loss = _build_mlp("pl")
    return main, loss


def test_planner_picks_cheapest_table_that_fits():
    """Loose budget -> least-communication table (replicated); tight
    budget -> nothing fits, smallest per-shard peak wins; the report
    carries per-candidate peaks and the comm-vs-compute verdict."""
    main, loss = _planner_program()
    table, rep = choose_rules(main, {"dp": 2, "mp": 4},
                              fetch_names=[loss.name], batch_size=16,
                              budget_mb=100.0)
    assert table.name == "replicated"
    assert [r["rules"] for r in rep] == \
        ["replicated", "mp_hidden", "mp_hidden_vocab"]
    assert all(r["fits"] for r in rep)
    assert sum(r["chosen"] for r in rep) == 1

    peaks = {r["rules"]: r["per_shard_peak_bytes"] for r in rep}
    # sharding strictly shrinks the per-shard static peak
    assert peaks["mp_hidden"] < peaks["replicated"]

    # a budget between the sharded and replicated peaks forces the
    # planner off the replicated table
    mid_mb = (peaks["mp_hidden"] + peaks["replicated"]) / 2 / (1 << 20)
    table2, rep2 = choose_rules(main, {"dp": 2, "mp": 4},
                                fetch_names=[loss.name], batch_size=16,
                                budget_mb=mid_mb)
    assert table2.name != "replicated"
    assert not next(r for r in rep2 if r["rules"] == "replicated")["fits"]

    # nothing fits: fallback to the smallest per-shard peak
    table3, rep3 = choose_rules(main, {"dp": 2, "mp": 4},
                                fetch_names=[loss.name], batch_size=16,
                                budget_mb=1e-6)
    assert table3.name == min(rep3,
                              key=lambda r: r["per_shard_peak_bytes"])["rules"]


def test_planner_respects_memory_budget_flag():
    """budget_mb=None reads FLAGS_memory_budget_mb."""
    main, loss = _planner_program()
    pt.set_flags({"FLAGS_memory_budget_mb": 4096})
    try:
        table, _ = choose_rules(main, {"dp": 2, "mp": 4},
                                fetch_names=[loss.name], batch_size=16)
        assert table.name == "replicated"
    finally:
        pt.set_flags({"FLAGS_memory_budget_mb": 0})


def test_plan_sharded_memory_divides_listed_vars():
    from paddle_tpu.analysis.memory import plan_memory, plan_sharded_memory
    main, loss = _planner_program()
    base = plan_memory(main, [loss.name], batch_size=16)
    specs = {n: (None, "mp") for n in
             ("pl_fc1.w_0", "pl_fc1.w_1", "pl_fc1.w_2")
             if main.global_block().has_var(n)}
    # find the real fc1 weight name (layer counters are process-global)
    block = main.global_block()
    specs = {n: (None, "mp") for n in block.vars
             if "pl_fc1.w" in n and getattr(block.var(n), "is_parameter",
                                            False)}
    assert specs
    sharded = plan_sharded_memory(main, [loss.name], batch_size=16,
                                  specs=specs,
                                  axis_sizes={"dp": 2, "mp": 4})
    assert sharded.resident_bytes < base.resident_bytes


# ---------------------------------------------------------------------------
# parity
# ---------------------------------------------------------------------------

def test_gspmd_mlp_parity_and_zero1():
    """mlp_adam under with_gspmd (forced mp_hidden + ZeRO-1) equals the
    single-chip run; the Adam moment lives dp-sharded in the scope."""
    single, _, _ = _train_mlp(lambda m, l: None, prefix="par")
    sharded, prog, moment = _train_mlp(
        lambda m, l: pt.CompiledProgram(m).with_gspmd(
            axes={"dp": 2, "mp": 4}, rules="mp_hidden", zero_stage=1),
        prefix="par")
    np.testing.assert_allclose(single, sharded, rtol=2e-4, atol=1e-6)
    stamp = prog._attrs["partition"]
    assert stamp["rules"] == "mp_hidden"
    assert stamp["params"], "mp_hidden must shard at least one param"
    assert moment is not None
    spec = moment.sharding.spec
    assert spec and spec[0] == "dp", f"ZeRO-1 moment not dp-sharded: {spec}"


@pytest.mark.slow
def test_gspmd_transformer_parity():
    """BERT pretrain on a dp×mp mesh under the most-sharded table equals
    the single-chip run (the ISSUE's acceptance model)."""
    from paddle_tpu.models import transformer as T

    def build():
        cfg = T.BertConfig(vocab_size=64, d_model=16, n_layer=2, n_head=4,
                           d_inner=32, max_pos=32, dropout=0.0)
        _, _, loss = T.build_bert_pretrain(cfg, seq_len=8)
        opt.AdamOptimizer(learning_rate=0.01).minimize(loss)
        return loss

    def feed_data(rng):
        return {"src_ids": rng.randint(1, 64, (8, 8)).astype("int64"),
                "pos_ids": np.tile(np.arange(8), (8, 1)).astype("int64"),
                "lm_label": rng.randint(0, 64, (8, 8)).astype("int64")}

    def run(compiled_fn, steps=3):
        main, start = Program(), Program()
        with program_guard(main, start), scope_guard(Scope()):
            loss = build()
            compiled = compiled_fn(main, loss)
            exe = Executor()
            main.random_seed = 5
            exe.run(pt.default_startup_program(), seed=11)
            rng = np.random.RandomState(3)
            out = []
            for _ in range(steps):
                lv, = exe.run(compiled, feed=feed_data(rng),
                              fetch_list=[loss.name])
                out.append(float(np.asarray(lv)))
            return out

    single = run(lambda m, l: None)
    sharded = run(lambda m, l: pt.CompiledProgram(m).with_gspmd(
        axes={"dp": 2, "mp": 4}, rules="mp_hidden_vocab"))
    np.testing.assert_allclose(single, sharded, rtol=2e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# fingerprint refusal
# ---------------------------------------------------------------------------

def _partitioned_fingerprint(rules):
    from paddle_tpu.analysis.verifier import collective_fingerprint
    main, start = Program(), Program()
    with program_guard(main, start), scope_guard(Scope()):
        _build_mlp("fp")
        partition_program(main, {"dp": 2, "mp": 4}, rules=rules)
    return collective_fingerprint(main)


def test_partition_fingerprint_carries_mesh_and_rules():
    fp1 = _partitioned_fingerprint("mp_hidden")
    fp2 = _partitioned_fingerprint("replicated")
    assert fp1.endswith("#rules=mp_hidden")
    assert fp2.endswith("#rules=replicated")
    assert fp1 != fp2
    # stamp-level token is deterministic in mesh shape + specs
    stamp = {"rules": "mp_hidden", "mesh_axes": {"dp": 2, "mp": 4},
             "params": {"w": (None, "mp")}}
    assert partition_fingerprint(stamp) == partition_fingerprint(dict(stamp))
    assert partition_fingerprint(None) is None


def test_step_barrier_refuses_divergent_rule_tables():
    """Two ranks whose planners chose different rule tables refuse at
    the step barrier, and the error NAMES both tables."""
    from paddle_tpu.distributed.coordinator import (GangClient,
                                                    GangCoordinator,
                                                    GangFingerprintError)
    fp0 = _partitioned_fingerprint("mp_hidden")
    fp1 = _partitioned_fingerprint("replicated")
    coord = GangCoordinator(world_size=2, heartbeat_timeout_s=30).start()
    c0 = GangClient(coord.address, rank=0, world_size=2).connect()
    c1 = GangClient(coord.address, rank=1, world_size=2).connect()
    errs = {}

    def arrive(c, fp):
        try:
            c.step_barrier(1, fp, timeout_s=10)
        except Exception as e:       # noqa: BLE001 — recorded for assert
            errs[c.rank] = e
    try:
        t = threading.Thread(target=arrive, args=(c0, fp0), daemon=True)
        t.start()
        time.sleep(0.15)
        arrive(c1, fp1)
        t.join(5)
        assert set(errs) == {0, 1}
        for e in errs.values():
            assert isinstance(e, GangFingerprintError)
            msg = str(e)
            assert "divergent GSPMD rule tables" in msg
            assert "'mp_hidden'" in msg and "'replicated'" in msg
    finally:
        c0.close()
        c1.close()
        coord.stop()


# ---------------------------------------------------------------------------
# sharded snapshot -> restore
# ---------------------------------------------------------------------------

def test_sharded_snapshot_restore_parity(tmp_path):
    """A checkpoint captured from a GSPMD run (sharded params + ZeRO-1
    state) restores through resume_or_init and continues with the exact
    losses of an uninterrupted run."""
    from paddle_tpu.checkpoint import CheckpointManager
    from paddle_tpu.resilience import resume_or_init

    from paddle_tpu.framework import unique_name

    def session(ckpt_dir, save_at=None, steps=6):
        main, start = Program(), Program()
        # fresh name generator per "process": sessions must agree on var
        # names or the restore-by-name matches nothing
        with unique_name.guard(), program_guard(main, start), \
                scope_guard(Scope()):
            loss = _build_mlp("ck")
            main.random_seed = 7
            start.random_seed = 7
            compiled = pt.CompiledProgram(main).with_gspmd(
                axes={"dp": 2, "mp": 4}, rules="mp_hidden", zero_stage=1)
            exe = Executor()
            ckpt = CheckpointManager(str(ckpt_dir))
            done = resume_or_init(ckpt, exe, startup_program=start,
                                  main_program=main)
            rng = np.random.RandomState(3)
            out = []
            for step in range(steps):
                xv = rng.rand(16, 8).astype(np.float32)
                yv = rng.randint(0, 4, (16, 1)).astype(np.int64)
                if step < done:
                    continue      # replay the rng stream, skip the step
                lv, = exe.run(compiled, feed={"x": xv, "y": yv},
                              fetch_list=[loss.name])
                out.append(float(np.asarray(lv)))
                if save_at is not None and step + 1 == save_at:
                    assert ckpt.save(step + 1, program=main)
                    break
            # a process drains its asynchronous save before it exits: until
            # the step's directory is renamed into place the next session
            # sees a torn save, and rightly starts from nothing
            ckpt.close()
            assert save_at is None or ckpt_dir.joinpath(str(save_at)).is_dir()
            return out

    full = session(tmp_path / "never")
    first = session(tmp_path / "ck", save_at=3)
    second = session(tmp_path / "ck")
    np.testing.assert_allclose(first + second, full, rtol=2e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# per-device HBM attribution + scope epoch
# ---------------------------------------------------------------------------

def test_per_device_nbytes_counts_shards():
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.hbm import per_device_nbytes
    from paddle_tpu.parallel.mesh import make_mesh
    mesh = make_mesh({"dp": 8})
    x = np.zeros((16, 4), np.float32)
    sharded = jax.device_put(x, NamedSharding(mesh, P("dp")))
    replicated = jax.device_put(x, NamedSharding(mesh, P()))
    assert per_device_nbytes(sharded) == x.nbytes // 8
    assert per_device_nbytes(replicated) == x.nbytes
    assert per_device_nbytes(x) == x.nbytes          # plain numpy


def test_scope_epoch_batch_writeback():
    s = Scope()
    assert s.epoch == 0
    s.set_var("a", np.ones(2))
    assert s.epoch == 0                  # per-name writes don't publish
    s.set_vars({"a": np.zeros(2), "b": np.ones(3)})
    assert s.epoch == 1                  # one bump per batch write-back
    assert s.materialize("b").shape == (3,)
    assert s.materialize("missing") is None


def test_executor_bumps_scope_epoch_once_per_step():
    main, start = Program(), Program()
    with program_guard(main, start), scope_guard(Scope()):
        loss = _build_mlp("ep")
        exe = Executor()
        exe.run(pt.default_startup_program(), seed=99)
        scope = global_scope()
        e0 = scope.epoch
        xv = np.random.rand(16, 8).astype(np.float32)
        yv = np.random.randint(0, 4, (16, 1)).astype(np.int64)
        exe.run(feed={"x": xv, "y": yv}, fetch_list=[loss.name])
        e1 = scope.epoch
        exe.run(feed={"x": xv, "y": yv}, fetch_list=[loss.name])
        assert e1 > e0
        assert scope.epoch == e1 + (e1 - e0)


# ---------------------------------------------------------------------------
# flags
# ---------------------------------------------------------------------------

def test_gspmd_flags_validate():
    pt.set_flags({"FLAGS_gspmd_mesh": "dp:2,mp:4"})
    try:
        with pytest.raises(ValueError, match="axis:size"):
            pt.set_flags({"FLAGS_gspmd_mesh": "dp=2"})
        with pytest.raises(ValueError, match="unknown rule table"):
            pt.set_flags({"FLAGS_gspmd_rules": "nonsense"})
        pt.set_flags({"FLAGS_gspmd_rules": "mp_hidden"})
    finally:
        pt.set_flags({"FLAGS_gspmd_mesh": "", "FLAGS_gspmd_rules": "auto"})


def test_rule_table_resolution():
    assert rule_table("mp_hidden").name == "mp_hidden"
    t = rule_table({"mlp": "mp"})
    assert isinstance(t, LogicalAxisRules) and t.rules == {"mlp": "mp"}
    assert rule_table(t) is t
    with pytest.raises(ValueError, match="unknown rule table"):
        rule_table("bogus")


# ---------------------------------------------------------------------------
# planner-choice observability: the choice counter and per-shard gauge
# ---------------------------------------------------------------------------

def test_choice_counter_increments_once_per_compile():
    """paddle_tpu_gspmd_rule_choices_total ticks exactly once per
    planner run, labeled with the chosen table and outcome — a compile
    that re-plans (or a counter wired into a per-step path by mistake)
    would break the fleet-wide 'how often does the planner fall back'
    signal."""
    from paddle_tpu import monitor
    ctr = monitor.REGISTRY.get("paddle_tpu_gspmd_rule_choices_total")
    main, loss = _planner_program()

    before = ctr.value(rules="replicated", outcome="fit")
    choose_rules(main, {"dp": 2, "mp": 4}, fetch_names=[loss.name],
                 batch_size=16, budget_mb=100.0)
    assert ctr.value(rules="replicated", outcome="fit") == before + 1

    # nothing fits -> one fallback tick for the most-sharded table, and
    # the fit cell did NOT move again
    fb_before = ctr.value(rules="mp_hidden_vocab", outcome="fallback")
    choose_rules(main, {"dp": 2, "mp": 4}, fetch_names=[loss.name],
                 batch_size=16, budget_mb=1e-6)
    assert ctr.value(rules="mp_hidden_vocab", outcome="fallback") == \
        fb_before + 1
    assert ctr.value(rules="replicated", outcome="fit") == before + 1

    # end to end: one with_gspmd(rules="auto") compile = one tick total
    total_before = sum(cell.get() for _, cell in ctr.series())
    _train_mlp(lambda m, l: pt.CompiledProgram(m).with_gspmd(
        axes={"dp": 2, "mp": 4}, rules="auto", zero_stage=1,
        fetch_names=[l.name], batch_size=16, budget_mb=100.0),
        steps=2, prefix="ctr")
    assert sum(cell.get() for _, cell in ctr.series()) == total_before + 1


def test_per_shard_gauge_tracks_shard_bytes_not_global():
    """paddle_tpu_gspmd_per_shard_peak_bytes reports the CHOSEN
    candidate's per-shard peak: for a sharded table that is strictly
    less than the replicated (global) peak — a gauge publishing global
    bytes would make every budget check read as over."""
    from paddle_tpu import monitor
    gauge = monitor.REGISTRY.get("paddle_tpu_gspmd_per_shard_peak_bytes")
    main, loss = _planner_program()
    _, rep = choose_rules(main, {"dp": 2, "mp": 4},
                          fetch_names=[loss.name], batch_size=16,
                          budget_mb=100.0)
    peaks = {r["rules"]: r["per_shard_peak_bytes"] for r in rep}
    # loose budget: replicated chosen, gauge = its (unsharded) peak
    assert gauge.value() == float(peaks["replicated"])

    # force a sharded choice: the gauge now tracks SHARD bytes
    mid_mb = (peaks["mp_hidden"] + peaks["replicated"]) / 2 / (1 << 20)
    table2, rep2 = choose_rules(main, {"dp": 2, "mp": 4},
                                fetch_names=[loss.name], batch_size=16,
                                budget_mb=mid_mb)
    chosen2 = next(r for r in rep2 if r["chosen"])
    assert table2.name != "replicated"
    assert gauge.value() == float(chosen2["per_shard_peak_bytes"])
    assert gauge.value() < float(peaks["replicated"])
