"""Device time of the last traced benchmark run by program op.

    python3 benchmark/run.py --workload <cell> ... --trace 1
    python3 tools/trace_by_op.py <cell> [role/op ...]

Reads the newest ``.xplane.pb`` under ``.cache/bench_trace/<cell>/`` with the
benchmark's own reducers (``benchmark/op_scopes.py``, ``part_scopes.py``) over
the whole recording, prints shares of device-busy time by ``pt.<role>/<op>``
scope, by part inside ``moe_ffn``, and which XLA operations make up each of
the largest scopes, and writes the same to
``chiprun_out/trace_by_op.<cell>.json``.  Shares, not milliseconds: multiply
by the run's ``step_device_ms.train``.  The three kinds of repeated work each
have their rows: the program's own recomputation is the role ``rc/`` among
the scopes (``framework/recompute.py``), XLA's rematerialised instructions
are ``remat_pct`` by the program op they belong to (``benchmark/
remat_scopes.py``: events named ``*.remat*``; "-" without a scope), and
forward work a generic vjp lowered again is ``forward_again_pct``.  Scopes
named after the cell (``fwd/short_conv rc/short_conv bwd/short_conv_grad``)
get every XLA operation under them listed, whatever their size.
"""

import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import (harness, op_scopes, part_scopes,  # noqa: E402
                       remat_scopes, trace_reduce)


#: scopes a model nests inside an op's own (``flash_attention``'s ``window``,
#: ``framework.name_scope`` tags; a tag nested in another is joined to it by
#: a dot, and the longer name comes first so that it is the one found)
TAGS = ("window", "mtp.mla_proj", "mtp.shared_expert", "mtp", "mla_proj",
        "shared_expert", "dense_ffn")

#: the scopes nested in ``moe_ffn``'s ``router`` and in its grad op's
ROUTER_PARTS = ("score", "select", "losses", "backward")


def _by_class(names, busy):
    """``{XLA operation class: [% of busy, instances]}`` of one scope's
    ``{operation name: seconds}``, largest first."""
    out = {}
    for name, sec in names.items():
        row = out.setdefault(trace_reduce.op_class(name), [0.0, 0])
        row[0] += 100 * sec / busy
        row[1] += 1
    return {k: [round(v[0], 3), v[1]] for k, v in sorted(
        out.items(), key=lambda kv: -kv[1][0])}


def main():
    cell = sys.argv[1]
    paths = sorted(glob.glob(os.path.join(
        harness.TRACE_DIR, cell, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        sys.exit(f"trace_by_op: no trace of {cell} under {harness.TRACE_DIR}")
    events = op_scopes.load_scoped_events(paths[-1])
    red = op_scopes.reduce_scopes(events)
    busy = red["busy_s"]
    share = lambda d: {k: round(100 * v / busy, 3) for k, v in sorted(  # noqa
        d.items(), key=lambda kv: -kv[1])}
    lo = min(e["start_ns"] for e in events)
    hi = max(e["start_ns"] + e["dur_ns"] for e in events)
    parts = part_scopes.reduce_parts(paths[-1], (lo, hi),
                                     part_scopes.MOE_PARTS)
    moe = {}
    for (role, op, part), s in parts.items():
        if op.startswith("moe_ffn"):
            moe[f"{role}/{part or '-'}"] = moe.get(f"{role}/{part or '-'}",
                                                   0.0) + s
    # the router's own parts (PR 64: the product and activation, the choice,
    # the losses; the grad op's closed-form backward)
    router = {}
    for (role, op, part), sec in part_scopes.reduce_parts(
            paths[-1], (lo, hi), ROUTER_PARTS).items():
        if op.startswith("moe_ffn") and part:
            router[f"{role}/{part}"] = router.get(f"{role}/{part}", 0.0) + sec
    # what a model tags inside an op's scope: the windowed layers of
    # flash_attention, the dense ops of a shared expert or a dense FFN,
    # latent attention's projections, a multi-token-prediction module
    tagged = {}
    for (role, op, part), sec in part_scopes.reduce_parts(
            paths[-1], (lo, hi), TAGS).items():
        if part or op.startswith("flash_attention"):
            key = f"{role}/{op}/{part or '-'}"
            tagged[key] = tagged.get(key, 0.0) + sec
    # XLA operation classes under each scope, by plain event time
    by = {}
    for e in events:
        sc = op_scopes.program_scope(e["scope"])
        key = "unscoped" if sc is None else f"{sc[0]}/{sc[1]}"
        d = by.setdefault(key, {})
        d[e["name"]] = d.get(e["name"], 0.0) + e["dur_ns"] / 1e9
    top = sorted(red["scoped"], key=lambda k: -red["scoped"][k])[:12]
    out = {"cell": cell, "busy_s": busy, "scoped_pct": share(red["scoped"]),
           "unscoped_pct": share(red["unscoped"]),
           "forward_again_pct": share(red["forward_again"]),
           "remat_pct": share({k or "-": v for k, v in
                               remat_scopes.reduce_remat(
                                   paths[-1], (lo, hi)).items()}),
           "moe_parts_pct": share(moe), "router_parts_pct": share(router),
           "tagged_pct": share(tagged),
           "xla_ops_pct": {k: dict(list(share(by[k]).items())[:6])
                           for k in top + ["unscoped"] if k in by},
           "asked_xla_ops_pct": {k: _by_class(by[k], busy)
                                 for k in sys.argv[2:] if k in by}}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"trace_by_op.{cell}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
