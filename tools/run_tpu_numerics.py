"""Run the on-hardware numerics sweep and write its artifact into a
directory given on the command line.

Usage (on a chip):
    python tools/run_tpu_numerics.py OUT_DIR

Writes OUT_DIR/tpu_numerics.json: per-test pass/fail, the error norms the
tests record via PADDLE_TPU_NUMERICS_OUT, and what tools/record_hbm.py
measured (device identity, the allocator's memory_stats counters, the
per-step HBM plans).  This parent never imports JAX: a chip belongs to one
process at a time, so each child (the pytest run, then record_hbm.py) owns
it in turn and device identity comes from the child's output.
"""
import json
import os
import re
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        sys.exit("usage: run_tpu_numerics.py OUT_DIR")
    out_dir = os.path.abspath(argv[0])
    os.makedirs(out_dir, exist_ok=True)
    norms_path = os.path.join(out_dir, "error_norms.jsonl")
    if os.path.exists(norms_path):
        os.unlink(norms_path)
    env = dict(os.environ)
    env["PADDLE_TPU_TEST_HW"] = "1"
    env["PADDLE_TPU_NUMERICS_OUT"] = norms_path
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-m", "tpu_hw",
         "tests/test_tpu_numerics.py", "-v", "--no-header", "-rN",
         "-p", "no:cacheprovider"],
        cwd=ROOT, capture_output=True, text=True, timeout=3600, env=env)

    tests = {}
    for line in r.stdout.splitlines():
        m = re.match(r"tests/test_tpu_numerics\.py::(\w+)\s+(PASSED|FAILED"
                     r"|SKIPPED|ERROR)", line)
        if m:
            tests[m.group(1)] = m.group(2)

    norms = []
    if os.path.exists(norms_path):
        with open(norms_path) as f:
            norms = [json.loads(l) for l in f if l.strip()]

    rh = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "record_hbm.py")],
        cwd=ROOT, capture_output=True, text=True, timeout=3600, env=env)
    hbm = next((json.loads(l) for l in reversed(rh.stdout.splitlines())
                if l.strip().startswith("{")),
               {"error": (rh.stderr or rh.stdout)[-300:]})

    artifact = {
        "device": hbm.get("device"),
        "pytest_rc": r.returncode,
        "record_hbm_rc": rh.returncode,
        "tests": tests,
        "n_passed": sum(1 for v in tests.values() if v == "PASSED"),
        "n_failed": sum(1 for v in tests.values() if v != "PASSED"),
        "error_norms": norms,
        "memory_stats": hbm.get("memory_stats"),
        "hbm_plans": hbm.get("plans"),
    }
    out = os.path.join(out_dir, "tpu_numerics.json")
    with open(out, "w") as f:
        json.dump(artifact, f, indent=1)
    print(json.dumps(artifact, indent=1))
    print(f"\nwrote {out}")
    if r.returncode != 0:
        print(r.stdout[-3000:])
    return r.returncode or rh.returncode


if __name__ == "__main__":
    sys.exit(main())
