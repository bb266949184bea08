"""Runs one cell as ``benchmark/run.py`` does, in this process, and then
prints the program's counters of the named families (a histogram's sum) as
one JSON line on standard error: what a run of the cell lowered, which no per-layer metric
has to read to be seen.  The result line stays the last of standard output.

    chiprun -- python3 tools/cell_counters.py paddle_tpu_flash_subtiles_total \
        -- --workload sdar_30b_a3b_bd_s8192_r64 --seed 7 --seconds 20 --trace 1

A family followed by ``{label,...}`` is summed over every other label: the
column alone.  ``'paddle_tpu_moe_lowerings_total{top_k,unsort,slot_sum}'``
says in which index order each ``moe_ffn`` lowering of the cell summed a
token's slots (PR 63: ``major`` in Nemotron-3-Nano's and Xing4.0's cells, the
two whose experts a token are no multiple of 8 on XLA's gather; ``minor``
everywhere else).
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv):
    cut = argv.index("--")
    families, run_args = argv[:cut], argv[cut + 1:]
    from benchmark import run
    rc = run.main(run_args)
    from paddle_tpu import monitor
    counted = {}
    for asked in families:
        name, _, by = asked.partition("{")
        by = [b for b in by.rstrip("}").split(",") if b]
        fam = monitor.REGISTRY.get(name)
        if fam is None:
            counted[asked] = None
            continue
        counted[asked] = {}
        for labels, cell in fam.series():
            key = ",".join(f"{k}={v}" for k, v in sorted(labels.items())
                           if not by or k in by)
            counted[asked][key] = counted[asked].get(key, 0) + (
                cell.get() if hasattr(cell, "get") else cell.sum)
    print("counters: " + json.dumps(counted), file=sys.stderr, flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
