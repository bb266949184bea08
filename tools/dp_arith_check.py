"""python3 tools/dp_arith_check.py [--chips 4] [--seed 7] [--out DIR]

The data-parallel step's arithmetic against the one-chip program's, on the
chips (the benchmark's four-chip cell compares no loss with a reference):
BERT-base at the benchmark's widths with dropout 0 and AMP Adam, the same 128
sequences once as one chip x 128 and once under ``with_data_parallel`` as
``chips`` x 32, both from the same seed's initial weights.  Prints the first
and the second step's loss of each and their relative differences, then the
per-shard lowerings the data-parallel trace counted
(``paddle_tpu_dp_local_lowerings_total``), and writes the compiled
data-parallel step's HLO to ``DIR/dp_step.hlo.txt``, and that of the
benchmark cell's own step (dropout on, 128 sequences a chip; compiled, never
run) to ``DIR/dp_step.cell.txt``, each with a summary of where its collectives
sit: which are inside a ``while`` body, and which computation holds the
all-reduce of the head's ``dW``, and where each all-reduce sits in the entry
computation's schedule (synchronous, or inside an async collective fusion).

Runs on whatever backend JAX has (a CPU with virtual devices rehearses it at
``--layers 2``); a number it prints is a device number only on a TPU.
"""

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

COLLECTIVE = re.compile(
    r" (all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")


def computations(hlo):
    """{computation name: its instruction lines} of an HLO module's text;
    the entry computation's name is prefixed ``ENTRY ``."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = re.match(r"^(ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if m and not line.startswith(" "):
            cur = comps.setdefault((m.group(1) or "") + m.group(2), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


def while_loops(hlo):
    """One ``(op_name, computation it sits in, collectives inside its
    body)`` per ``while`` of the module; the body is followed through the
    computations it calls."""
    comps = computations(hlo)
    by_name = {k.replace("ENTRY ", ""): v for k, v in comps.items()}
    out = []
    for comp, lines in comps.items():
        for line in lines:
            if " while(" not in line:
                continue
            todo = [re.search(r"body=%?([\w.\-]+)", line).group(1)]
            seen, inside = set(), []
            while todo:
                c = todo.pop()
                if c in seen or c not in by_name:
                    continue
                seen.add(c)
                for inner in by_name[c]:
                    if COLLECTIVE.search(inner):
                        inside.append(inner.strip())
                    todo += re.findall(
                        r"(?:body|condition|to_apply|calls)=%?([\w.\-]+)",
                        inner)
            op = re.search(r'op_name="([^"]*)"', line)
            out.append((op.group(1) if op else "?", comp, inside))
    return out


class _Caught(Exception):
    pass


def caught_step(run):
    """``(compiled block, (feeds, ro, rw, seed))`` of the first step that
    ``run()`` would execute: the executor's own arguments, caught at the
    call that would have run the step, which then does not run."""
    from paddle_tpu.framework import executor as E
    caught, call = {}, E._CompiledBlock.__call__

    def catch(self, f, ro, rw, seed):
        caught.update(cb=self, args=(f, ro, rw, seed))
        raise _Caught()

    E._CompiledBlock.__call__ = catch
    try:
        run()
    except _Caught:
        pass
    finally:
        E._CompiledBlock.__call__ = call
    return caught["cb"], caught["args"]


def _mbytes(shape):
    size = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "f16": 2}
    tot = 0
    for dt, dims in re.findall(r"(f32|bf16|s32|u32|f16)\[([\d,]*)\]", shape):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        tot += n * size[dt]
    return tot / 1e6


def all_reduce_schedule(hlo):
    """``(number of entry instructions, rows)`` of a scheduled module: one
    ``[first, last, form, MB, op_name]`` per all-reduce, in schedule order.
    ``form`` is ``sync`` for an ``all-reduce`` instruction of the entry
    computation, ``fused xN`` for one inside an async collective fusion
    whose chain of N continuation fusions spans entry instructions
    ``first..last`` (each fusion of the chain holds a clone of the
    all-reduce with the same ``channel_id``)."""
    comps = computations(hlo)
    by_name = {k.replace("ENTRY ", ""): v for k, v in comps.items()}
    entry = next((v for k, v in comps.items() if k.startswith("ENTRY ")), [])

    def inside(comp, seen):
        if comp in seen or comp not in by_name:
            return []
        seen.add(comp)
        got = []
        for line in by_name[comp]:
            if " all-reduce(" in line:
                got.append(line)
            for c in re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", line):
                got += inside(c, seen)
        return got

    def describe(line):
        body = line.split(" = ", 1)[1]
        op = re.search(r'op_name="([^"]*)"', line)
        return (_mbytes(body[:body.index(" all-reduce")]),
                re.sub(r"^jit\(\w+\)/", "", op.group(1) if op else "?")[:64])

    rows, chains = [], {}
    for i, line in enumerate(entry):
        if re.search(r" all-reduce(-start)?\(", line):
            rows.append([i, i, "sync", *describe(line)])
        call = re.search(r"calls=%?(async_collective_fusion[\w.\-]*)", line)
        for inner in inside(call.group(1), set()) if call else ():
            key = re.search(r"channel_id=(\d+)", inner).group(1)
            if key in chains:
                chains[key][1] = i
                chains[key][2] += 1
            else:
                chains[key] = [i, i, 1, *describe(inner)]
    rows += [[a, b, f"fused x{n}", mb, op]
             for a, b, n, mb, op in chains.values()]
    return len(entry), sorted(rows)


def hlo_summary(hlo, vocab):
    """Lines saying where the collectives of a compiled step sit."""
    out = [f"while under {op} in {comp}: {len(inside)} collectives in its "
           "body" + "".join("\n    " + i[:100] for i in inside)
           for op, comp, inside in while_loops(hlo)]
    n, seen = {}, set()
    for comp, lines in computations(hlo).items():
        for line in lines:
            m = COLLECTIVE.search(line)
            if not m:
                continue
            # an async collective fusion's continuation fusions each hold a
            # clone of their collective: one channel is one collective
            channel = re.search(r"channel_id=(\d+)", line)
            if channel and (m.group(1), channel.group(1)) in seen:
                continue
            seen.add((m.group(1), channel and channel.group(1)))
            n[m.group(1)] = n.get(m.group(1), 0) + 1
            result = line[:m.start()]
            if f",{vocab}]" in result or f"[{vocab}," in result \
                    or "fused_lm_head_ce" in line:
                op = re.search(r'op_name="([^"]*)"', line)
                out.append(f"head collective in {comp}: "
                           f"{line.strip()[:160]} ... op_name="
                           f"{op.group(1) if op else '?'}")
    out.append(f"collectives in the module: {n}")
    # where the scheduler put each all-reduce, and in which form (PR 28)
    n_entry, rows = all_reduce_schedule(hlo)
    forms = {}
    for _, _, form, mb, _ in rows:
        key = "sync" if form.startswith("sync") else "fused"
        forms[key] = forms.get(key, 0) + 1
    out.append(f"all-reduces of the entry computation ({n_entry} "
               f"instructions): {forms}")
    out += [f"  @{a}..{b} {form} {mb:.2f} MB {op}"
            for a, b, form, mb, op in rows if mb >= 0.5]
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--chips", type=int, default=4)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    args = ap.parse_args(argv)

    import numpy as np
    import jax
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt
    from paddle_tpu.framework import (Program, Scope, executor as E,
                                      program_guard, scope_guard)
    from paddle_tpu.models import transformer as T
    from paddle_tpu.framework.executor import DP_LOCAL_CTR
    from benchmark import harness
    from benchmark.models import _train, bert_base

    dev = jax.devices()[0]
    print(f"dp_arith_check: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}", flush=True)
    config = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "bert_base.json")))
    traffic = json.load(open(os.path.join(
        ROOT, "benchmark", "traffic", "mlm_s128.json")))
    if args.layers:
        config["num_hidden_layers"] = args.layers
    cfg = bert_base._bert_config(config, dropout=0.0)
    seq, n_mask, batch = traffic["seq_len"], traffic["masked_per_seq"], 128
    rng = _train.rng_of(args.seed)
    feeds = [bert_base.make_batch(rng, cfg, batch, seq, n_mask)
             for _ in range(2)]

    def build():
        scope, main_p, startup = Scope(), Program(), Program()
        with scope_guard(scope), program_guard(main_p, startup):
            _, _, loss = T.build_bert_pretrain(
                cfg, seq, fused_head=True, arange_pos=True,
                masked_gather=n_mask)
            pt.amp.decorate(opt.AdamOptimizer(
                learning_rate=traffic["learning_rate"])).minimize(loss)
            exe = pt.Executor()
            exe.run(startup, scope=scope, seed=harness.exe_seed(args.seed))
        return exe, scope, main_p, loss

    def two_steps(exe, scope, prog, loss):
        return [float(np.asarray(exe.run(prog, feed=f, scope=scope,
                                         fetch_list=[loss.name])[0]))
                for f in feeds]

    exe1, scope1, main1, loss1 = build()
    exe4, scope4, main4, loss4 = build()
    worst = max(float(np.abs(np.asarray(scope1.find_var(p.name), np.float32)
                             - np.asarray(scope4.find_var(p.name),
                                          np.float32)).max())
                for p in main1.all_parameters())
    print(f"dp_arith_check: initial weights of the two builds differ by at "
          f"most {worst:g}", flush=True)
    one = two_steps(exe1, scope1, main1, loss1)
    del exe1, scope1

    # the data-parallel step's HLO, from the arguments the executor hands it
    texts, call = {}, E._CompiledBlock.__call__

    def record(self, f, ro, rw, seed):
        if "hlo" not in texts:
            texts["hlo"] = self.jitted.lower(f, ro, rw, seed
                                             ).compile().as_text()
        return call(self, f, ro, rw, seed)

    before = {k: DP_LOCAL_CTR.value(op=k[0], engaged=k[1])
              for k in list(DP_LOCAL_CTR._series)}
    E._CompiledBlock.__call__ = record
    try:
        dp = two_steps(exe4, scope4, pt.CompiledProgram(main4)
                       .with_data_parallel(loss_name=loss4.name,
                                           places=args.chips), loss4)
    finally:
        E._CompiledBlock.__call__ = call
    # the lowering above and the executor's own trace each count once
    counted = {f"{k[0]}/engaged={k[1]}":
               (DP_LOCAL_CTR.value(op=k[0], engaged=k[1])
                - before.get(k, 0)) / 2
               for k in list(DP_LOCAL_CTR._series)}

    del exe4, scope4

    # the benchmark cell's own step (dropout on, 128 sequences a chip),
    # compiled from the arguments the executor hands it and never run
    m = bert_base.build_train(config, traffic, args.seed, args.chips,
                              dev.platform == "tpu")
    cell, cell_args = caught_step(lambda: m["exe"].run(
        m["program"], feed=_train.put_ring(m["ring"][:1], args.chips)[0],
        fetch_list=[m["loss"]], scope=m["scope"], return_numpy=False))
    texts["cell"] = cell.jitted.lower(*cell_args).compile().as_text()

    rel = [abs(a - b) / abs(a) for a, b in zip(one, dp)]
    print(f"dp_arith_check: one chip x {batch}: losses {one}")
    print(f"dp_arith_check: {args.chips} chips x {batch // args.chips}: "
          f"losses {dp}")
    print(f"dp_arith_check: relative difference first step {rel[0]:.3e}, "
          f"second step {rel[1]:.3e}")
    print(f"dp_arith_check: per-shard lowerings per trace {counted}")
    os.makedirs(args.out, exist_ok=True)
    for key, what in (("hlo", "the compared data-parallel step"),
                      ("cell", "the benchmark cell's step")):
        path = os.path.join(args.out, f"dp_step.{key}.txt")
        with open(path, "w") as f:
            f.write(texts[key])
        print(f"dp_arith_check: HLO of {what} -> {path}")
        for line in hlo_summary(texts[key], cfg.vocab_size):
            print("dp_arith_check:  ", line)
    ok = rel[0] <= 1e-4 and rel[1] <= 1e-3
    print(json.dumps({"ok": ok, "first_rel": rel[0], "second_rel": rel[1],
                      "one_chip": one, "dp": dp,
                      "platform": dev.platform, "chips": args.chips}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
