#!/usr/bin/env bash
# CI driver (ref paddle/scripts/paddle_build.sh, scoped to this repo):
# native build, full test suite on the virtual 8-device CPU mesh, the
# standalone C++ train demo, the planes' smoke tools, and the API-spec dump.
set -euo pipefail
cd "$(dirname "$0")/.."

# The persistent XLA compile cache needs no setup here: the package places
# it at import (JAX_COMPILATION_CACHE_DIR if set, else .cache/xla_compile
# in this checkout), so repeated CI rounds skip the first-compile cost.

echo "== native runtime build =="
make -C native
make -C native demo_trainer

echo "== native unit tests (ref *_test.cc gtest suite analog) =="
make -C native native_test
./native/native_test

echo "== test suite (8-device CPU mesh) =="
python -m pytest tests/ -q

echo "== C++ train demo =="
tmp=$(mktemp -d)
JAX_PLATFORMS=cpu python tools/export_demo_program.py "$tmp"
./native/demo_trainer "$tmp"
rm -rf "$tmp"

echo "== multichip dryrun (virtual 8-device mesh, driver contract) =="
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python __graft_entry__.py --multichip 8

echo "== wheel build + clean-venv install_check =="
wheeldir=$(mktemp -d); venvdir=$(mktemp -d)
pip wheel . -w "$wheeldir" --no-deps --no-build-isolation -q
python -m venv "$venvdir"
# zero-egress image: deps (jax/numpy/...) come from the base env via a
# .pth, not the index — the wheel itself installs clean
sitedir=$("$venvdir/bin/python" -c 'import site; print(site.getsitepackages()[0])')
python -c 'import sysconfig; print(sysconfig.get_paths()["purelib"])' > "$sitedir/_basedeps.pth"
"$venvdir/bin/pip" install -q --no-deps "$wheeldir"/paddle_tpu-*.whl
(cd "$venvdir" && JAX_PLATFORMS=cpu "$venvdir/bin/python" -c \
    "import paddle_tpu; paddle_tpu.install_check.run_check()")
rm -rf "$wheeldir" "$venvdir"

echo "== telemetry smoke (chrome trace + metrics export + live /metrics scrape validation) =="
tel_tmp=$(mktemp -d)
JAX_PLATFORMS=cpu python tools/telemetry_smoke.py "$tel_tmp"

echo "== latency report (offline phase decomposition from the smoke's trace) =="
python tools/latency_report.py "$tel_tmp/trace.json"
python tools/latency_report.py "$tel_tmp/trace.json" --json | python -c '
import json, sys
rep = json.load(sys.stdin)
assert rep["total_requests"] >= 1, rep
g = rep["groups"][0]
assert "dispatch" in g["phases"] and g["e2e"]["p99_ms"] > 0, g
print("latency report OK: %d request(s) decomposed" % rep["total_requests"])'
rm -rf "$tel_tmp"

echo "== resilience smoke (fault injection + retries + ckpt integrity) =="
JAX_PLATFORMS=cpu python tools/resilience_smoke.py

echo "== gang smoke (socket liveness plane: kill -9 a rank, launcher respawns, gang reconverges) =="
JAX_PLATFORMS=cpu python tools/gang_smoke.py

echo "== concurrency lint (guarded fields, signal handlers, threads, finalizers) =="
python tools/lint_concurrency.py

echo "== verifier smoke (known-bad programs caught at optimize time) =="
JAX_PLATFORMS=cpu python tools/verifier_smoke.py

echo "== memory-planner smoke (static analysis over the saved demo program) =="
an_tmp=$(mktemp -d)
JAX_PLATFORMS=cpu python tools/export_demo_program.py "$an_tmp" > /dev/null
JAX_PLATFORMS=cpu python tools/analyze.py --memory --verify --json \
    "$an_tmp/main_program" | python -c '
import json, sys
out = json.load(sys.stdin)
mem = out["memory"]
assert mem["peak_bytes"] > 0 and mem["top_ops"], mem
assert mem["peak_bytes"] >= mem["resident_bytes"], mem
assert out["verify"]["errors"] == 0, out["verify"]
print(f"memory plan OK: peak {mem[\"peak_bytes\"]} B at {mem[\"peak_op\"]}")'
rm -rf "$an_tmp"

echo "== fusion smoke (zero-fusion-when-disabled, verifier-clean-when-enabled, loss parity, autotune cache) =="
JAX_PLATFORMS=cpu python tools/fusion_smoke.py

echo "== numerics smoke (in-graph stats, NaN poison -> anomaly + capture window + checkpoint quarantine) =="
JAX_PLATFORMS=cpu python tools/numerics_smoke.py

echo "== comms smoke (static plan vs measured bytes, straggler-wait decomposition, zero added host blocks) =="
JAX_PLATFORMS=cpu python tools/comms_smoke.py

echo "== hbm smoke (live accounting zero host blocks, memory.oom drill -> forensics dump, KV-page churn exact) =="
JAX_PLATFORMS=cpu python tools/hbm_smoke.py

echo "== gspmd smoke (planner pick under memory pressure, sharded-vs-single-chip parity, ZeRO-1 opt_state gauge) =="
JAX_PLATFORMS=cpu python tools/gspmd_smoke.py

echo "== sharding smoke (mp_hidden analyzes 0-unexplained, overcommitted table refused pre-dispatch, plan == measured bytes) =="
JAX_PLATFORMS=cpu python tools/sharding_smoke.py

echo "== serving smoke (continuous batching, 2 tenants, fault absorption, SIGTERM drain) =="
JAX_PLATFORMS=cpu python tools/serving_smoke.py

echo "== fleet smoke (2-replica router drain/SIGKILL re-route, coordinator standby failover, autoscaler scale drill, manifest never torn) =="
# fast subset: one pass of each chaos drill (drain, replica SIGKILL,
# primary-coordinator SIGKILL, autoscaler spike->spawn / kill->repair /
# idle->retire); the fault-injection kill matrix — including the failed
# replica spawn and the coordinator failover under a running autoscaler
# — runs under --full from the slow-marked tests in tests/test_fleet.py
JAX_PLATFORMS=cpu python tools/fleet_smoke.py

echo "== xprof smoke (fixture parse + live capture -> summary.json keys, measured vs analytic MFU band) =="
# 1) the checked-in synthetic window parses to the exact designed
#    attribution (step join, op classes, idle fraction, xplane agreement)
JAX_PLATFORMS=cpu python tools/xprof.py --window tests/fixtures/xprof_window \
    --flops_per_step 5.75e8 --peak_flops 1e12 \
    --share matmul=0.8,elementwise=0.2 --json | python -c '
import json, sys
s = json.load(sys.stdin)
assert s["n_steps"] == 2 and [r["step"] for r in s["steps"]] == [100, 101], s["steps"]
assert abs(s["idle_frac"] - 0.425) < 1e-9, s["idle_frac"]
assert abs(s["per_class_share"]["matmul"] - 0.72) < 1e-9, s["per_class_share"]
assert abs(s["measured"]["mfu_measured"] - 1.0) < 1e-6, s["measured"]
assert s["xplane_kernel_ms"] == {"dot.1": 0.9, "fusion.2": 0.2}, s.get("xplane_kernel_ms")
assert s["divergence"]["wasted_headroom"], "empty headroom ranking"
print("xprof fixture OK: 2 steps, idle %.1f%%, measured MFU %.2f" % (
    100 * s["idle_frac"], s["measured"]["mfu_measured"]))'
# 2) a real CPU capture round-trips through the post-close hook: the
#    window summary exists, carries the schema, and measured/analytic
#    agree within a band loose enough for CPU dispatch slack
JAX_PLATFORMS=cpu python -c '
import json, os, tempfile
import numpy as np
import paddle_tpu as pt
from paddle_tpu import layers, monitor, profiler
from paddle_tpu.framework import Executor, Program, program_guard
from paddle_tpu.framework.scope import Scope, scope_guard
sdir = tempfile.mkdtemp(prefix="ci_xprof_")
scope = Scope()
with scope_guard(scope), program_guard(Program(), Program()):
    x = layers.data("x", shape=[128], dtype="float32")
    h = layers.fc(x, size=256, act="relu")
    loss = layers.mean(layers.fc(h, size=64))
    pt.optimizer.SGD(0.01).minimize(loss)
    exe = Executor()
    exe.run(pt.default_startup_program(), scope=scope)
    feed = {"x": np.ones((32, 128), np.float32)}
    for _ in range(3):
        exe.run(feed=feed, fetch_list=[loss.name], scope=scope)
    profiler.SAMPLER.configure(2, 3, sdir, 2)
    for _ in range(8):
        exe.run(feed=feed, fetch_list=[loss.name], scope=scope)
    profiler.SAMPLER.close()
    profiler.SAMPLER.configure(0, 4, "", 8)
windows = json.load(open(os.path.join(sdir, "manifest.json")))["windows"]
dirs = [w["dir"] for w in windows]
assert len(dirs) == len(set(dirs)), f"manifest duplicates: {dirs}"
s = json.load(open(os.path.join(windows[-1]["dir"], "summary.json")))
for key in ("steps", "per_class_ms", "per_class_share", "idle_frac",
            "kernels", "measured", "divergence"):
    assert key in s, key
m = s["measured"]
assert m["mfu_measured"] and m["mfu_measured"] > 0, m
fam = monitor.REGISTRY.get("paddle_tpu_step_mfu_measured")
assert fam is not None and fam.value() > 0
assert monitor.metrics_digest().get("mfu_m"), "mfu_m missing from digest"
# measured >= analytic-over-span by construction (busy <= span), and on
# CPU the two stay within a generous band (dispatch slack dominates)
ratio = m["mfu_measured"] / m["mfu_analytic_over_span"]
assert 1.0 <= ratio < 100.0, ratio
import shutil; shutil.rmtree(sdir, ignore_errors=True)
print("xprof live capture OK: measured %.2f%%, analytic-over-span %.2f%%, mfu_m in digest" % (
    100 * m["mfu_measured"], 100 * m["mfu_analytic_over_span"]))'

echo "== API surface vs committed spec =="
if ! JAX_PLATFORMS=cpu python tools/print_signatures.py --diff API.spec; then
    echo "public API changed; review the diff above and regenerate with:"
    echo "    python tools/print_signatures.py > API.spec"
    exit 1
fi

echo "CI OK"
