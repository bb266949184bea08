"""python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of its standard output, the contract's JSON object.
Fails (non-zero, no result) without a TPU holding the chips the cell asks for.
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark import harness
    spec = harness.load_spec()
    cell = harness.find(spec["workloads"], args.workload, "workload")
    import paddle_tpu  # noqa: F401  (places the compile cache; no backend yet)
    harness.require_tpu(cell["chips"])
    import jax
    dev = jax.devices()[0]
    harness.log(f"workload={args.workload} seed={args.seed} "
                f"seconds={args.seconds} trace={args.trace} "
                f"platform={dev.platform} device_kind={dev.device_kind} "
                f"count={len(jax.devices())} "
                f"compile_cache={jax.config.jax_compilation_cache_dir}")
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace),
                              t_process_start=T_PROCESS_START, spec=spec)
    for line in result["compared"]:
        print(f"bench: {line}", file=sys.stderr)
    sys.stderr.flush()
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
