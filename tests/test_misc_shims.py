"""WeightedAverage / net_drawer / legacy Downpour API shims
(ref python/paddle/fluid/average.py, net_drawer.py,
python/paddle/fluid/distributed/{downpour,node,ps_instance}.py)."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.distributed import downpour
from paddle_tpu.framework.core import Program, program_guard


def test_weighted_average():
    wa = fluid.WeightedAverage()
    with pytest.raises(ValueError):
        wa.eval()
    wa.add(1.0, weight=1)
    wa.add(np.array([3.0, 3.0]), weight=3)
    assert wa.eval() == pytest.approx((1 + 9) / 4)
    wa.reset()
    with pytest.raises(ValueError):
        wa.add("nope", 1)


def test_net_drawer_writes_dot(tmp_path):
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = layers.data("x", shape=[4], dtype="float32")
        layers.fc(x, size=2)
    path = fluid.net_drawer.draw_graph(startup, main,
                                       output=str(tmp_path / "net.dot"))
    text = open(path).read()
    assert "digraph" in text and "mul" in text


def test_downpour_sgd_builds_ps_descriptor():
    main, startup = Program(), Program()
    with program_guard(main, startup):
        ids = layers.data("ids", shape=[1], dtype="int64")
        emb = layers.embedding(ids, size=[100, 8], is_sparse=True)
        dense = layers.data("dense", shape=[4], dtype="float32")
        h = layers.fc(layers.concat(
            [layers.reshape(emb, [-1, 8]), dense], axis=1), size=1)
        cost = layers.mean(layers.square(h))
        opt = downpour.DownpourSGD(learning_rate=0.01, window=1)
        ps_param, skipped = opt.minimize([cost])
    assert len(ps_param.server_param.sparse_tables) == 1
    assert ps_param.server_param.sparse_tables[0].slot_key_vars == \
        [ids.name]
    assert len(ps_param.server_param.dense_tables) == 1
    dense_params = ps_param.server_param.dense_tables[0].param_vars
    assert any("fc" in p for p in dense_params)
    # embedding param handled by the sparse table, not the dense one
    assert not any("emb" in p for p in dense_params)
    assert ps_param.program_configs[0]["pull_sparse_table_id"] == [0]
    assert "sgd" in skipped


def test_ps_instance_roles(monkeypatch):
    monkeypatch.setenv("TRAINING_ROLE", "PSERVER")
    monkeypatch.setenv("PADDLE_PSERVER_ENDPOINTS",
                       "127.0.0.1:7000,127.0.0.1:7001")
    monkeypatch.setenv("PADDLE_CURRENT_ENDPOINT", "127.0.0.1:7001")
    inst = downpour.PaddlePSInstance()
    assert inst.is_server() and not inst.is_worker()
    assert inst.get_server_index() == 1

    monkeypatch.setenv("TRAINING_ROLE", "TRAINER")
    monkeypatch.setenv("PADDLE_TRAINER_ID", "0")
    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "2")
    inst = downpour.PaddlePSInstance()
    assert inst.is_first_worker() and inst.get_worker_num() == 2


def test_multi_slot_data_generator(capsys):
    from paddle_tpu.incubate.data_generator import (
        MultiSlotDataGenerator, MultiSlotStringDataGenerator)

    class Gen(MultiSlotDataGenerator):
        def generate_sample(self, line):
            def local_iter():
                ints = [int(t) for t in line.split()]
                yield [("words", ints[:-1]), ("label", [ints[-1]])]
            return local_iter

    import io, sys
    gen = Gen()
    gen.set_batch(2)
    sys.stdin = io.StringIO("1 2 3 0\n4 5 6 1\n")
    try:
        gen.run_from_stdin()
    finally:
        sys.stdin = sys.__stdin__
    out = capsys.readouterr().out.splitlines()
    # MultiSlot text format: "count v..." per slot (native data_feed.cc)
    assert out[0] == "3 1 2 3 1 0"
    assert out[1] == "3 4 5 6 1 1"
    assert gen._proto_info == [("words", "uint64"), ("label", "uint64")]

    sgen = MultiSlotStringDataGenerator()
    assert sgen._gen_str([("a", ["x", "y"])]) == "2 x y\n"

    import pytest
    with pytest.raises(ValueError):
        Gen()._gen_str("not a list")
    with pytest.raises(ValueError):
        Gen()._gen_str([("a", [])])


def test_flags_system(monkeypatch):
    """ref platform/flags.cc + __bootstrap__ FLAGS_* env passthrough."""
    import jax
    import paddle_tpu.flags as F
    try:
        assert fluid.get_flags("FLAGS_allocator_strategy") == \
            {"FLAGS_allocator_strategy": "auto_growth"}
        fluid.set_flags({"FLAGS_eager_delete_tensor_gb": "2.5"})
        assert F.globals()["FLAGS_eager_delete_tensor_gb"] == 2.5
        F.globals()["FLAGS_benchmark"] = True
        assert fluid.get_flags(["FLAGS_benchmark"])["FLAGS_benchmark"] \
            is True
        fluid.set_flags({"FLAGS_benchmark": False})
        import pytest
        with pytest.raises(ValueError):
            fluid.set_flags({"FLAGS_not_a_flag": 1})
        # a bad entry must not half-apply the good ones
        with pytest.raises(ValueError):
            fluid.set_flags({"FLAGS_check_nan_inf": True,
                             "FLAGS_typo": 1})
        # check_nan_inf is a framework-level sanitizer (executor binds a
        # finite-check per op output — tests/test_sanitizers.py); it must
        # NOT flip jax_debug_nans, which would abort the step instead
        fluid.set_flags({"FLAGS_check_nan_inf": True})
        assert not jax.config.jax_debug_nans
        fluid.set_flags({"FLAGS_check_nan_inf": False})
        # env bootstrap — malformed values warn and are ignored
        monkeypatch.setenv("FLAGS_paddle_num_threads", "4")
        monkeypatch.setenv("FLAGS_rpc_retry_times", "not_an_int")
        import warnings
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            F._bootstrap_from_env()
        assert any("FLAGS_rpc_retry_times" in str(x.message) for x in w)
        assert F.globals()["FLAGS_paddle_num_threads"] == 4
    finally:
        # process-global state: always restore defaults for later tests
        F._values.update(F._DEFAULTS)
        jax.config.update("jax_debug_nans", False)
        jax.config.update("jax_debug_infs", False)


def test_xla_compile_cache_flag(tmp_path):
    """FLAGS_xla_compile_cache_dir places jax's persistent compilation
    cache (first-compile is the TPU analog of the reference's CUDA
    kernel-build cost) — unless JAX_COMPILATION_CACHE_DIR is set, which
    always wins; emptying the flag returns to the checkout default, never
    to "off" (tests/test_compile_cache_placement.py has the full matrix)."""
    import os

    import jax
    from paddle_tpu.device import DEFAULT_COMPILE_CACHE_DIR
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    d = str(tmp_path / "xla_cache")
    fluid.set_flags({"FLAGS_xla_compile_cache_dir": d})
    try:
        assert jax.config.jax_compilation_cache_dir == (env_dir or d)
    finally:
        fluid.set_flags({"FLAGS_xla_compile_cache_dir": ""})
    assert jax.config.jax_compilation_cache_dir == (
        env_dir or DEFAULT_COMPILE_CACHE_DIR)
