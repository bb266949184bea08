#!/usr/bin/env python
"""Whole-program static analysis over a SAVED program, no dispatch:
the verifier's full diagnostic report (``--verify``), the static HBM
peak-memory plan (``--memory``), the graph-fusion candidate report
(``--fusion``), and/or the GSPMD sharding analysis (``--sharding``) —
the offline entry point to the same ``paddle_tpu.analysis`` suite
``compiler.optimize`` runs inline.

Usage::

    python tools/analyze.py [--verify] [--memory] [--fusion] [--json]
        [--sharding --mesh dp:2,mp:2 [--rules TABLE] [--zero N]]
        [--fetch name[,name...]] [--batch N] PROGRAM

``--sharding`` applies a ``LogicalAxisRules`` table offline (program
blobs don't carry the runtime partition stamp) and reports the
propagated PartitionSpec per var, every priced reshard edge
(kind / mesh axis / payload bytes through the ring model), the
spec_conflict / shard_divisibility / mesh_axis_overuse diagnostics,
and the PER-SHARD static HBM peak (``plan_sharded_memory``).
``--mesh`` is required; ``--rules`` defaults to ``auto`` (the planner
picks under ``FLAGS_memory_budget_mb``); ``--zero 1`` prices ZeRO-1
optimizer traffic.  Error-severity findings exit 1 — the same refusal
``compiler.optimize`` enforces.

``PROGRAM`` is either a serialized program blob
(``Program.serialize_to_string`` — e.g. ``main_program`` from
``tools/export_demo_program.py``) or an inference-model directory
(``io.save_inference_model`` — its ``__model__``'s saved fetch list is
the default ``--fetch``).  With none of ``--verify``/``--memory``/
``--fusion``, verify+memory run.  ``--batch`` resolves symbolic (-1)
dims in the memory plan and the fusion cost ranking (default 1: a
per-example lower bound).

``--fusion`` is REPORT-ONLY (no rewrite is applied): every candidate
with its legality verdict and per-class roofline rank.

Exit status: 0 clean, 1 when ``--verify`` finds error-severity
diagnostics, 2 on usage errors.
"""

import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _load(path: str):
    """(program, default_fetch_names) from a blob file or a
    save_inference_model directory."""
    from paddle_tpu.framework.core import Program
    p = Path(path)
    if p.is_dir():
        model = p / "__model__"
        if not model.exists():
            raise SystemExit(
                f"analyze: {path!r} is a directory without __model__ "
                "(not a save_inference_model dir)")
        payload = json.loads(model.read_bytes().decode("utf-8"))
        prog = Program.parse_from_string(
            json.dumps(payload).encode("utf-8"))
        return prog, tuple(payload.get("fetch_names", ()))
    return Program.parse_from_string(p.read_bytes()), ()


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or any(a in ("-h", "--help") for a in argv):
        print(__doc__)
        return 0 if argv else 2
    want_verify = "--verify" in argv
    want_memory = "--memory" in argv
    want_fusion = "--fusion" in argv
    want_sharding = "--sharding" in argv
    as_json = "--json" in argv
    fetch = ()
    batch = 1
    mesh = None
    rules = "auto"
    zero = 0
    paths = []
    skip = set()
    for i, a in enumerate(argv):
        if i in skip:
            continue
        if a == "--fetch":
            if i + 1 >= len(argv):
                print("analyze: --fetch needs a name list",
                      file=sys.stderr)
                return 2
            fetch = tuple(x for x in argv[i + 1].split(",") if x)
            skip.add(i + 1)
        elif a == "--batch":
            if i + 1 >= len(argv):
                print("analyze: --batch needs an int", file=sys.stderr)
                return 2
            batch = int(argv[i + 1])
            skip.add(i + 1)
        elif a == "--mesh":
            if i + 1 >= len(argv):
                print("analyze: --mesh needs axis:size[,axis:size...]",
                      file=sys.stderr)
                return 2
            try:
                mesh = {k: int(v) for k, v in
                        (kv.split(":") for kv in argv[i + 1].split(","))}
            except ValueError:
                print(f"analyze: bad --mesh spec {argv[i + 1]!r}",
                      file=sys.stderr)
                return 2
            skip.add(i + 1)
        elif a == "--rules":
            if i + 1 >= len(argv):
                print("analyze: --rules needs a table name",
                      file=sys.stderr)
                return 2
            rules = argv[i + 1]
            skip.add(i + 1)
        elif a == "--zero":
            if i + 1 >= len(argv):
                print("analyze: --zero needs 0 or 1", file=sys.stderr)
                return 2
            zero = int(argv[i + 1])
            skip.add(i + 1)
        elif a.startswith("--"):
            if a not in ("--verify", "--memory", "--fusion",
                         "--sharding", "--json"):
                print(f"analyze: unknown flag {a!r}", file=sys.stderr)
                return 2
        else:
            paths.append(a)
    if len(paths) != 1:
        print("analyze: exactly one PROGRAM path required",
              file=sys.stderr)
        return 2
    if want_sharding and mesh is None:
        print("analyze: --sharding needs --mesh axis:size[,...] "
              "(saved blobs carry no partition stamp)", file=sys.stderr)
        return 2
    if not want_verify and not want_memory and not want_fusion \
            and not want_sharding:
        want_verify = want_memory = True

    try:
        program, saved_fetch = _load(paths[0])
    except (OSError, ValueError) as e:
        print(f"analyze: cannot load {paths[0]!r}: {e}", file=sys.stderr)
        return 2
    fetch = fetch or saved_fetch

    from paddle_tpu import debugger
    from paddle_tpu.analysis import (analyze_program, plan_memory,
                                     verify_program)

    out = {"program": paths[0], "fetch": list(fetch)}
    rc = 0
    result = None
    plan = None
    if want_verify:
        result = verify_program(program, fetch)
        if result.errors():
            rc = 1
        out["verify"] = {
            "ok": result.ok,
            "errors": len(result.errors()),
            "warnings": len(result.warnings()),
            "diagnostics": [
                {"check": d.check, "severity": d.severity,
                 "message": d.message, "op_type": d.op_type,
                 "op_index": d.op_index, "var": d.var, "block": d.block}
                for d in result.diagnostics],
            "collective_fingerprint": result.collective_fingerprint,
            "int64_static": sorted(result.int64_static),
            "int64_dynamic": sorted(result.int64_dynamic),
            "dead_ops": list(result.dead_ops),
            "dead_subblock_ops": {
                str(k): list(v)
                for k, v in result.dead_subblock_ops.items()},
        }
    if want_memory:
        plan = plan_memory(program, fetch, batch_size=batch)
        out["memory"] = {
            "batch": batch,
            "peak_bytes": plan.peak_bytes,
            "peak_op": plan.peak_op,
            "peak_pos": plan.peak_pos,
            "resident_bytes": plan.resident_bytes,
            "steady_bytes": plan.steady_bytes,
            "top_ops": [
                {"pos": p, "op": t, "live_bytes": b,
                 "transient_bytes": tr}
                for p, t, b, tr in plan.top_ops(10)],
        }
    fusion_report = None
    if want_fusion:
        fusion_report = analyze_program(program, fetch, batch_size=batch)
        out["fusion"] = fusion_report.as_dict()
    shard_plan = None
    shard_peak = None
    if want_sharding:
        from paddle_tpu.analysis import sharding as _shard
        from paddle_tpu.analysis.memory import plan_sharded_memory
        from paddle_tpu.parallel import partitioner as _part
        stamp = _part.partition_program(program, mesh, rules=rules,
                                        fetch_names=fetch,
                                        batch_size=batch)
        stamp["zero_stage"] = zero
        shard_plan = _shard.plan_sharding(program, fetch,
                                          batch_size=batch)
        shard_peak = plan_sharded_memory(
            program, fetch, batch_size=batch,
            specs={**stamp["params"], **stamp["activations"]},
            axis_sizes=stamp["mesh_axes"])
        n_err = sum(1 for d in shard_plan.diagnostics
                    if d.severity == "error")
        if n_err:
            rc = 1
        out["sharding"] = {
            "rules": shard_plan.rules,
            "mesh": dict(shard_plan.mesh_axes),
            "zero_stage": shard_plan.zero_stage,
            "batch": batch,
            "specs": {k: list(v)
                      for k, v in sorted(shard_plan.specs.items())},
            "edges": [
                {"direction": e.direction, "kind": e.kind,
                 "mesh_axis": e.mesh_axis, "var": e.var,
                 "payload_bytes": e.payload_bytes,
                 "wire_bytes": e.wire_bytes, "reason": e.reason,
                 "exact": e.exact} for e in shard_plan.edges],
            "n_edges": len(shard_plan.edges),
            "n_unexplained": len(shard_plan.unexplained),
            "payload_bytes": shard_plan.payload_bytes,
            "wire_bytes": shard_plan.wire_bytes,
            "est_ms": shard_plan.est_ms,
            "errors": n_err,
            "diagnostics": [
                {"check": d.check, "severity": d.severity,
                 "message": d.message, "var": d.var}
                for d in shard_plan.diagnostics],
            "fingerprint": shard_plan.fingerprint,
            "per_shard_peak_bytes": int(shard_peak.peak_bytes),
            "per_shard_steady_bytes": int(shard_peak.steady_bytes),
        }
    if as_json:
        print(json.dumps(out, indent=2, sort_keys=True))
        return rc
    if want_verify:
        r = out["verify"]
        print(f"== verify: {'OK' if r['ok'] else 'FAILED'} "
              f"({r['errors']} error(s), {r['warnings']} warning(s)) ==")
        if result.diagnostics:
            print(debugger.format_diagnostics(result.diagnostics))
        if r["collective_fingerprint"]:
            print(f"collective fingerprint: "
                  f"{r['collective_fingerprint']}")
        if r["int64_static"] or r["int64_dynamic"]:
            print(f"int64 feeds: static={r['int64_static']} "
                  f"dynamic={r['int64_dynamic']}")
    if want_memory and plan is not None:
        print("== memory ==")
        print(plan.report())
    if want_sharding and shard_plan is not None:
        r = out["sharding"]
        print(f"== sharding: {'FAILED' if r['errors'] else 'OK'} "
              f"({r['n_edges']} edge(s), {r['n_unexplained']} "
              f"unexplained, {r['errors']} error(s)) ==")
        print(shard_plan.report())
        if shard_plan.diagnostics:
            print(debugger.format_diagnostics(shard_plan.diagnostics))
        for var, spec in sorted(shard_plan.specs.items()):
            print(f"  spec {var:<40} {tuple(spec)}")
        print(f"per-shard peak: {r['per_shard_peak_bytes']} B "
              f"(steady {r['per_shard_steady_bytes']} B)")
    if fusion_report is not None:
        r = out["fusion"]
        print(f"== fusion: {r['applied']} applicable candidate(s) of "
              f"{len(r['candidates'])} matched ==")
        for c in r["candidates"]:
            extra = f" rule={c['rule']}" if c.get("rule") else ""
            print(f"  [{c['verdict']:>13}] {c['pattern']:<22} "
                  f"@ {c['anchor']} rank={c['rank']:.3f}{extra}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
