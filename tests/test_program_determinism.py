"""A program is a pure function of what built it: the AMP training program
of every cell's builder (forward, ``append_backward``, optimizer ops, and
the executor's fusion pass where it applies), built at toy widths in
processes that differ only in ``PYTHONHASHSEED``, comes out op for op the
same.  The persistent compile cache keys on the lowered step, so a program
whose op order follows a ``set`` of names is compiled anew in every process
(ISSUE 47: Xing4.0's four streams' gradient sums, 55 s of every set-up).

The file is its own child: ``python tests/test_program_determinism.py
<builder> [--lowered]`` prints one line an op and the sha256 of those lines
last (``--lowered``: the sha256 of the CPU-lowered step's text with debug
info, which is what the cache key covers)."""

import hashlib
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: seconds a child may take (import, build, fingerprint: 10-25 s here)
CHILD_LIMIT = 240


# -- the child ---------------------------------------------------------------

def _toy(module, fn):
    """A cell's toy ``(config, traffic)`` from its rehearsal in
    ``tests/benchmark``."""
    import importlib
    sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))
    return getattr(importlib.import_module(module), fn)()


def _joyai_plain():
    """JoyAI's step without recomputation, as ``tools/joyai_step_aot.py``
    builds it without ``--recompute``."""
    import joyai_step_aot
    from paddle_tpu import optimizer as opt
    opt.RecomputeOptimizer = joyai_step_aot._NoRecompute
    return _toy("test_joyai_cell", "toy_joyai")


#: builder -> (adapter under benchmark/models, its toy config and traffic)
BUILDERS = {
    "xing4": ("xing4_29b_a4b", lambda: _toy("test_xing4_cell", "toy_xing")),
    "joyai": ("joyai_llm_flash", _joyai_plain),
    "joyai_recompute": ("joyai_llm_flash",
                        lambda: _toy("test_joyai_cell", "toy_joyai")),
    "trinity": ("trinity_mini",
                lambda: _toy("test_trinity_cell", "toy_trinity")),
    "smallthinker": ("smallthinker_21b_a3b",
                     lambda: _toy("test_smallthinker_cell",
                                  "toy_smallthinker")),
    "lfm2": ("lfm2_8b_a1b", lambda: _toy("test_lfm2_cell", "toy_lfm2")),
    "solar": ("solar_open2_250b",
              lambda: _toy("test_solar_open2_cell", "toy_solar")),
    "ling": ("ling3_flash_vl",
             lambda: _toy("test_ling3_cell", "toy_ling")),
    "nemotron3": ("nemotron3_nano_30b_a3b",
                  lambda: _toy("test_nemotron3_cell", "toy_nemotron")),
    "olmoe": ("olmoe_1b_7b", lambda: _toy("test_olmoe_cell", "toy_olmoe")),
    "sdar": ("sdar_30b_a3b", lambda: _toy("test_sdar_cell", "toy_sdar")),
    "bert_fused": ("bert_base",
                   lambda: _toy("test_benchmark_rehearsal", "toy_bert")),
    "resnet50": ("resnet50",
                 lambda: _toy("test_benchmark_rehearsal", "toy_resnet")),
}


def _attr(v):
    from paddle_tpu.framework.core import Block
    if isinstance(v, Block):
        return f"block:{v.idx}"
    if isinstance(v, (set, frozenset)):
        return sorted(map(_attr, v))
    if isinstance(v, (list, tuple)):
        return [_attr(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _attr(x) for k, x in v.items()}
    if hasattr(v, "tolist"):
        return v.tolist()
    return v if isinstance(v, (str, int, float, bool, type(None))) else repr(v)


def op_lines(program):
    """One line an op, in program order: block, type, inputs and outputs by
    slot and attributes, each in the op's own order."""
    return [json.dumps([b.idx, op.type, list(op.inputs.items()),
                        list(op.outputs.items()),
                        [(k, _attr(v)) for k, v in op.attrs.items()]])
            for b in program.blocks for op in b.ops]


def child(builder, lowered):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    from benchmark import harness
    from paddle_tpu.analysis import fusion
    import dp_arith_check
    adapter, toy = BUILDERS[builder]
    config, traffic = toy()
    m = harness.load_module("models", adapter).build_train(
        config, traffic, 11, 1, False)
    feed = m["ring"][0]
    if lowered:
        cb, args = dp_arith_check.caught_step(lambda: m["exe"].run(
            m["program"], feed=feed, fetch_list=[m["loss"]],
            scope=m["scope"], return_numpy=False))
        text = cb.jitted.lower(*args).as_text(debug_info=True)
        print(hashlib.sha256(text.encode()).hexdigest())
        return 0
    # the program as the executor lowers it: its fusion pass applied (BERT's
    # dense epilogues and embedding LayerNorm; nothing in the other cells)
    program = fusion.fuse_program(
        m["program"], (m["loss"],),
        feed_shapes={n: tuple(getattr(v, "shape", ())) for n, v in
                     feed.items()})
    lines = op_lines(program)
    print("\n".join(lines))
    print(hashlib.sha256("\n".join(lines).encode()).hexdigest())
    return 0


# -- the tests ---------------------------------------------------------------

def _children(builder, hash_seeds, *flags):
    """The child's output under each hash seed, the children side by side,
    each under its own limit.  A child that did not come back sound (killed,
    or late under a whole run's other workers: three fresh JAX processes a
    test beside five more workers' own) runs once more, alone: what the
    tests hold is the children's TEXT, not how loaded the machine was."""
    def start(s):
        return subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), builder, *flags],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            cwd=ROOT,
            env=dict(os.environ, PYTHONHASHSEED=str(s), JAX_PLATFORMS="cpu"))

    def finish(p):
        try:
            out, err = p.communicate(timeout=CHILD_LIMIT)
            return p.returncode, out, err
        except subprocess.TimeoutExpired:
            p.kill()
            out, err = p.communicate()
            return "late", out, err

    procs = [start(s) for s in hash_seeds]
    outs = []
    try:
        for s, p in zip(hash_seeds, procs):
            rc, out, err = finish(p)
            if rc != 0:
                rc, out, err = finish(start(s))
            assert rc == 0, (builder, s, rc, err[-2000:])
            outs.append(out.strip().split("\n"))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return outs


@pytest.mark.parametrize("builder", sorted(BUILDERS))
def test_the_training_program_is_the_same_in_every_process(builder):
    first, *rest = _children(builder, (1, 2, 3))
    assert len(first) > 10                  # ops, and the sha256 last
    for seed, other in zip((2, 3), rest):
        assert len(first) == len(other), (builder, seed)
        at = next((i for i, (a, b) in enumerate(zip(first, other))
                   if a != b), None)
        assert at is None, (
            f"{builder}: PYTHONHASHSEED=1 and ={seed} build different "
            f"programs, first at op {at}: {first[at][:300]} | "
            f"{other[at][:300]}")


def test_xing4s_lowered_step_is_one_text_in_two_processes():
    """What the compile cache keys on: the lowered step with its debug info
    (locations and ``pt.<role>/<op>`` scopes)."""
    (one,), (two,) = _children("xing4", (1, 2), "--lowered")
    assert len(one) == 64 and one == two


if __name__ == "__main__":
    sys.exit(child(sys.argv[1], "--lowered" in sys.argv[2:]))
