#!/usr/bin/env python
"""Fleet chaos drill (CI gate): a REAL 2-replica + router (+ gang
coordinator with warm standby) topology under an open-loop client, with
scripted kills — asserting the fleet drops nothing:

1. ``drain``  — SIGTERM one replica mid-load: the replica's guard-path
   drain finishes its in-flight work and exits 0; the router holds it
   out of placement and re-routes (``reason="drain"``); the client sees
   ZERO failures and the router ledger sums exactly
   (completed == admitted, failed == rejected == 0).
2. ``kill``   — SIGKILL one replica mid-request: in-flight idempotent
   requests replay on the survivor (``reason="dead"`` re-routes ≥ 1),
   zero client-visible failures, p99 bounded during the failover.
3. ``coord``  — full topology (primary + standby coordinator, replicas
   heart-beating as ``role=replica``, a rank-0 publisher committing
   manifest steps): SIGKILL the PRIMARY coordinator mid-commit-loop.
   The standby promotes (epoch-fenced), ranks and publisher fail over
   with zero errors, serving traffic is untouched, and the durable
   MANIFEST parses strictly at every instant (never torn) and never
   regresses.
4. ``scale``  — the self-driving-fleet drill: a load spike on a
   1-replica fleet makes the autoscaler count EXACTLY one scale-up and
   spawn a second replica (p99 back under the calibrated SLO objective,
   zero client-visible failures); a replica SIGKILL'd under load is
   replaced to restore the target; sustained idle retires exactly one
   replica through the drain path (retired child exits 0, ledger sums).

``--full`` adds the fault-injection matrix on top: a torn router
forward (``router.forward:once``), a torn coordinator frame
(``coordinator.frame:once@5``), a failed replica spawn
(``autoscaler.spawn:once`` — the controller backs off, keeps shedding
engaged, retries, never recounts the decision), and a primary-
coordinator SIGKILL under the running autoscaler (the controller keeps
ticking through the epoch-bumped promotion with zero scale flaps).

Subprocess protocol: this file re-invokes itself with ``--role
replica`` / ``--role coordinator``; children print ``READY <addr>`` on
stdout once serving.
"""

import argparse
import os
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
sys.path.insert(0, str(Path(__file__).resolve().parent))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402

REPLICA_BUCKETS = (32,)
#: big enough that one request costs ~10ms+ on CPU — the drill needs a
#: REAL drain window (requests in flight at SIGTERM) and a real
#: failover window (requests in flight at SIGKILL), not a model so
#: small every request completes before the kill signal propagates
REPLICA_CFG = dict(vocab_size=64, d_model=64, n_layer=4, n_head=4,
                   d_inner=256, max_pos=64, dropout=0.0)
SEQ = 24
HB_TIMEOUT = 0.3          # coordinator liveness + standby promotion clock


# ---------------------------------------------------------------------------
# child roles
# ---------------------------------------------------------------------------

class _DelayExecutor:
    """Executor proxy adding a fixed service time per dispatch.  The
    drill's model is tiny on CPU — socket overhead, not compute,
    dominates, so the scheduler queue never builds and the autoscaler's
    ``srv_q`` gate has nothing to read.  A per-batch delay makes the
    replica behave like a genuinely saturated device: concurrent
    requests pile up in the scheduler queue (the real overload signal)
    and a spike pushes p99 well past the calibrated objective."""

    def __init__(self, inner, delay_s):
        self._inner = inner
        self._delay_s = float(delay_s)

    def run(self, *a, **kw):
        time.sleep(self._delay_s)
        return self._inner.run(*a, **kw)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def replica_main(args) -> int:
    from serving_smoke import _build
    from paddle_tpu.framework.executor import Executor
    from paddle_tpu.serving.fleet import ReplicaEndpoint
    from paddle_tpu.serving.server import InferenceServer
    cfg, scope, factory = _build(REPLICA_CFG)
    exe = Executor()
    if args.batch_delay_ms > 0:
        exe = _DelayExecutor(exe, args.batch_delay_ms / 1000.0)
    srv = InferenceServer(factory, scope, buckets=REPLICA_BUCKETS,
                          max_batch=4, executor=exe).start()
    srv.warmup()
    ep = ReplicaEndpoint(srv, port=args.port,
                         replica_id=f"replica-{args.rank}").start()
    client = None
    if args.coord:
        from paddle_tpu.distributed.coordinator import GangClient
        client = GangClient(address=args.coord, rank=args.rank,
                            world_size=args.world,
                            heartbeat_interval_s=0.1, role="replica",
                            endpoint=ep.address)
        client.connect().start_heartbeat()
    print(f"READY {ep.address}", flush=True)
    # blocks until SIGTERM, then drains: exit 0 iff zero dropped
    code = srv.serve_until_terminated(poll_s=0.02, drain_timeout_s=20.0)
    if client is not None:
        client.close()
    ep.stop()
    return code


def coordinator_main(args) -> int:
    from paddle_tpu.distributed.coordinator import GangCoordinator
    coord = GangCoordinator(args.world, port=args.port,
                            heartbeat_timeout_s=HB_TIMEOUT,
                            manifest_dir=args.manifest_dir or None,
                            standby_of=args.standby_of or None).start()
    print(f"READY {coord.address}", flush=True)
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda s, f: stop.set())
    signal.signal(signal.SIGINT, lambda s, f: stop.set())
    stop.wait()
    coord.stop()
    return 0


# ---------------------------------------------------------------------------
# driver plumbing
# ---------------------------------------------------------------------------

def _spawn(role: str, extra_args, env_extra=None):
    """Start one child role; returns (proc, address) after READY."""
    cmd = [sys.executable, "-u", __file__, "--role", role] + \
        [str(a) for a in extra_args]
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=sys.stderr, text=True)
    deadline = time.monotonic() + 120.0
    while True:
        line = proc.stdout.readline()
        if line.startswith("READY "):
            return proc, line.split(None, 1)[1].strip()
        if not line and proc.poll() is not None:
            raise RuntimeError(f"{role} child died before READY "
                               f"(exit {proc.returncode})")
        if time.monotonic() > deadline:
            proc.kill()
            raise RuntimeError(f"{role} child never became READY")


def _wait_exit(proc, timeout_s=30.0) -> int:
    try:
        return proc.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        proc.kill()
        raise


def _kill_all(procs):
    for p in procs:
        if p.poll() is None:
            p.kill()
    for p in procs:
        try:
            p.wait(timeout=5.0)
        except subprocess.TimeoutExpired:
            pass


class OpenLoopLoad:
    """N client threads firing inference at the router back-to-back
    (small think time); records per-request latency and every error."""

    def __init__(self, router, n_clients=6, think_s=0.005,
                 shed_ok=False):
        self.router = router
        self.n_clients = n_clients
        self.think_s = think_s
        #: the scale drill's shed-tolerant mode: an ``slo_shed``
        #: admission rejection is the autoscaler's arbitration verdict,
        #: not a failure — recorded separately so the ledger still sums
        self.shed_ok = shed_ok
        self._stop = threading.Event()
        self._mu = threading.Lock()
        self.latencies = []          # guarded-by: _mu
        self.errors = []             # guarded-by: _mu
        self.sheds = []              # guarded-by: _mu
        self._threads = []

    def start(self):
        for i in range(self.n_clients):
            t = threading.Thread(target=self._client, args=(i,),
                                 daemon=True, name=f"fleet-client-{i}")
            t.start()
            self._threads.append(t)
        return self

    def _client(self, idx):
        n = 0
        while not self._stop.is_set():
            feeds = {"src_ids": ((np.arange(SEQ) + idx + n) % 40)
                     .astype("int64")}
            t0 = time.perf_counter()
            try:
                self.router.infer(f"tenant{idx % 2}", feeds,
                                  seq_len=SEQ, timeout_s=15.0)
                with self._mu:
                    self.latencies.append(time.perf_counter() - t0)
            except Exception as e:
                msg = repr(e)
                with self._mu:
                    if self.shed_ok and "slo_shed" in msg:
                        self.sheds.append(msg)
                    else:
                        self.errors.append(msg)
            n += 1
            self._stop.wait(self.think_s)

    def stop(self):
        self._stop.set()
        for t in self._threads:
            t.join(timeout=20.0)

    def p99_ms(self) -> float:
        with self._mu:
            lats = sorted(self.latencies)
        if not lats:
            return 0.0
        return lats[min(int(0.99 * len(lats)), len(lats) - 1)] * 1e3

    def counts(self):
        with self._mu:
            return len(self.latencies), list(self.errors)


def _ctr(counter, **labels) -> float:
    try:
        return float(counter.value(**labels))
    except Exception:
        return 0.0


def _assert_ledger(router, load, scenario):
    """completed == admitted exactly; zero failures anywhere."""
    done, errors = load.counts()
    snap = router.snapshot()
    assert not errors, f"[{scenario}] client-visible failures: " \
                       f"{errors[:5]} ({len(errors)} total)"
    assert snap["failed"] == 0 and snap["rejected"] == 0, \
        f"[{scenario}] router ledger has failures: {snap}"
    assert snap["completed"] == snap["admitted"] == done, \
        f"[{scenario}] ledger does not sum: admitted=" \
        f"{snap['admitted']} completed={snap['completed']} " \
        f"client-done={done}"
    return done, snap


# ---------------------------------------------------------------------------
# scenarios
# ---------------------------------------------------------------------------

def scenario_drain(full=False):
    """SIGTERM one replica under load: zero failures, drain re-routes,
    drained replica exits 0."""
    from paddle_tpu import monitor as M
    from paddle_tpu.serving.fleet import FleetRouter
    drain0 = _ctr(M.FLEET_REROUTE_CTR, reason="drain")
    r0, a0 = _spawn("replica", ["--rank", 0])
    r1, a1 = _spawn("replica", ["--rank", 1])
    # round_robin: placement keeps offering the SIGTERM'd replica until
    # its draining refusal comes back, so the reason="drain" re-route
    # ledger is deterministic (least_loaded would steer traffic away
    # from the drained replica's non-empty queue before it ever refuses)
    router = FleetRouter([a0, a1], policy="round_robin",
                         digest_ttl_s=1.0).start()
    load = OpenLoopLoad(router).start()
    try:
        time.sleep(1.5)               # both replicas take traffic
        r0.send_signal(signal.SIGTERM)
        time.sleep(2.5)               # drain + re-routed load
        load.stop()
        code = _wait_exit(r0)
        assert code == 0, f"[drain] SIGTERM'd replica exited {code} " \
                          "(dropped in-flight work)"
        done, snap = _assert_ledger(router, load, "drain")
        drains = _ctr(M.FLEET_REROUTE_CTR, reason="drain") - drain0
        assert drains >= 1, "[drain] no drain re-route was recorded"
        states = {a: r["state"] for a, r in snap["replicas"].items()}
        print(f"fleet drain OK: {done} requests, 0 failed, "
              f"{drains:.0f} drain re-route(s), replica exit 0, "
              f"states={states}")
    finally:
        load.stop()
        router.stop()
        _kill_all([r0, r1])


def scenario_kill(full=False, inject_forward=False):
    """SIGKILL one replica mid-request: in-flight requests replay on
    the survivor, zero failures, p99 bounded."""
    from paddle_tpu import monitor as M
    from paddle_tpu import resilience as R
    from paddle_tpu.serving.fleet import FleetRouter
    dead0 = _ctr(M.FLEET_REROUTE_CTR, reason="dead")
    fault0 = _ctr(R._FAULT_CTR, site="router.forward")
    if inject_forward:
        from paddle_tpu.flags import set_flags
        set_flags({"FLAGS_fault_inject": "router.forward:once"})
    r0, a0 = _spawn("replica", ["--rank", 0])
    r1, a1 = _spawn("replica", ["--rank", 1])
    router = FleetRouter([a0, a1], digest_ttl_s=1.0).start()
    load = OpenLoopLoad(router).start()
    name = "kill+inject" if inject_forward else "kill"
    try:
        time.sleep(1.5)
        r0.kill()                     # SIGKILL mid-request
        time.sleep(2.5)
        load.stop()
        done, snap = _assert_ledger(router, load, name)
        deads = _ctr(M.FLEET_REROUTE_CTR, reason="dead") - dead0
        assert deads >= 1, f"[{name}] no dead re-route was recorded"
        p99 = load.p99_ms()
        assert p99 < 10000.0, f"[{name}] p99 unbounded: {p99:.0f}ms"
        if inject_forward:
            faults = _ctr(R._FAULT_CTR, site="router.forward") - fault0
            assert faults == 1, f"[{name}] injected ledger: {faults}"
        print(f"fleet {name} OK: {done} requests, 0 failed, "
              f"{deads:.0f} dead re-route(s), p99 {p99:.0f}ms")
    finally:
        if inject_forward:
            from paddle_tpu.flags import set_flags
            set_flags({"FLAGS_fault_inject": ""})
        load.stop()
        router.stop()
        _kill_all([r0, r1])


def scenario_coord(full=False, inject_frame=False):
    """SIGKILL the primary coordinator mid-commit-loop: the standby
    promotes epoch-fenced, publisher + replicas fail over with zero
    errors, serving traffic untouched, MANIFEST never torn."""
    import tempfile
    from paddle_tpu import monitor as M
    from paddle_tpu.distributed.coordinator import GangClient
    from paddle_tpu.distributed.env import parse_manifest
    from paddle_tpu.serving.fleet import FleetRouter
    from gangtop import fetch_status

    mdir = tempfile.mkdtemp(prefix="pt_fleet_gang_")
    world = 3                         # rank 0 publisher + 2 replicas
    env_extra = ({"FLAGS_fault_inject": "coordinator.frame:once@5"}
                 if inject_frame else None)
    prim, prim_addr = _spawn(
        "coordinator", ["--world", world, "--manifest_dir", mdir],
        env_extra=env_extra)
    stand, stand_addr = _spawn(
        "coordinator", ["--world", world, "--manifest_dir", mdir,
                        "--standby_of", prim_addr])
    coord_addr = f"{prim_addr},{stand_addr}"
    r0, a0 = _spawn("replica", ["--rank", 1, "--world", world,
                                "--coord", coord_addr])
    r1, a1 = _spawn("replica", ["--rank", 2, "--world", world,
                                "--coord", coord_addr])
    router = FleetRouter([a0, a1], digest_ttl_s=1.0).start()
    load = OpenLoopLoad(router).start()
    name = "coord+inject" if inject_frame else "coord"

    pub = GangClient(address=coord_addr, rank=0, world_size=world,
                     heartbeat_interval_s=0.1).connect().start_heartbeat()
    pub_errors, published = [], [0]
    torn, regressed = [], []
    stop = threading.Event()

    def publisher():
        step = 0
        while not stop.is_set():
            step += 1
            try:
                pub.publish(step)
                published[0] = step
            except Exception as e:
                pub_errors.append(repr(e))
            stop.wait(0.05)

    def manifest_watch():
        """The torn-manifest probe: at EVERY instant the durable file
        either does not exist yet or parses strictly, and the step
        never regresses across the failover."""
        last = 0
        path = os.path.join(mdir, "MANIFEST")
        while not stop.is_set():
            time.sleep(0.002)
            try:
                with open(path) as f:
                    text = f.read()
            except OSError:
                continue
            try:
                step = parse_manifest(text)
            except ValueError as e:
                torn.append(f"torn manifest: {e!r} text={text!r}")
                continue
            if step is not None:
                if step < last:
                    regressed.append((last, step))
                last = step

    threads = [threading.Thread(target=publisher, daemon=True),
               threading.Thread(target=manifest_watch, daemon=True)]
    for t in threads:
        t.start()
    try:
        time.sleep(1.5)               # commits + heartbeats flowing
        prim.kill()                   # SIGKILL mid-commit-loop
        time.sleep(4.0)               # promotion + post-failover load
        stop.set()
        load.stop()
        for t in threads:
            t.join(timeout=5.0)
        assert not pub_errors, f"[{name}] publisher failures " \
            f"across failover: {pub_errors[:3]}"
        assert not torn, f"[{name}] {torn[:2]}"
        assert not regressed, f"[{name}] manifest regressed: {regressed}"
        done, snap = _assert_ledger(router, load, name)
        st = fetch_status(stand_addr)
        assert st.get("coord_role") == "primary", \
            f"[{name}] standby never promoted: {st.get('coord_role')}"
        assert int(st.get("epoch", 0)) >= 1, \
            f"[{name}] promotion without epoch bump: {st.get('epoch')}"
        assert int(st.get("manifest") or 0) >= published[0] - 1, \
            f"[{name}] manifest lost commits: {st.get('manifest')} " \
            f"vs published {published[0]}"
        with open(os.path.join(mdir, "EPOCH")) as f:
            fence = int(f.read().strip())
        assert fence >= 1, f"[{name}] EPOCH fence not stamped: {fence}"
        roles = {r: e.get("role") for r, e in st["ranks"].items()}
        alive = all(e["alive"] or e["finished"]
                    for r, e in st["ranks"].items()
                    if roles.get(r) == "replica")
        assert alive, f"[{name}] replicas lost after failover: " \
                      f"{st['ranks']}"
        print(f"fleet {name} OK: {done} requests 0 failed, "
              f"{published[0]} steps published 0 errors, standby "
              f"promoted epoch={st['epoch']}, manifest "
              f"{st.get('manifest')} never torn, roles={roles}")
    finally:
        stop.set()
        load.stop()
        router.stop()
        try:
            pub.close(goodbye=False)
        except Exception:
            pass
        _kill_all([prim, stand, r0, r1])


# ---------------------------------------------------------------------------
# scale: the self-driving-fleet drill (autoscaler closed loop)
# ---------------------------------------------------------------------------

#: every label pair the autoscaler counts — the drill asserts the WHOLE
#: ledger, so a decision that leaked into the wrong reason still fails
_SCALE_LABELS = (("up", "burn_queue"), ("up", "death"), ("up", "oom"),
                 ("down", "idle"), ("down", "surplus"))


def _scale_totals():
    from paddle_tpu import monitor as M
    return {(d, r): _ctr(M.FLEET_SCALE_CTR, dir=d, reason=r)
            for d, r in _SCALE_LABELS}


def _wait_until(cond, deadline_s, what):
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.1)
    raise AssertionError(f"timed out after {deadline_s:.0f}s waiting "
                         f"for {what}")


class _ScaleRig:
    """Plumbing for the autoscaler drills: a FleetRouter
    over subprocess replicas, with spawn/retire closures wired into a
    FleetAutoscaler.  The spawn closure speaks the same ``READY <addr>``
    protocol :class:`paddle_tpu.distributed.launch.ReplicaLauncher`
    does, and the retire closure is the launcher's drain contract
    (SIGTERM + wait — the child exits 0 iff it dropped nothing)."""

    def __init__(self, max_replicas=2, interval_s=0.25,
                 shed_enabled=False, backoff_s=None, delay_ms=20.0):
        from paddle_tpu.serving.autoscaler import (AutoscalerPolicy,
                                                   FleetAutoscaler)
        from paddle_tpu.serving.fleet import FleetRouter
        self._mu = threading.Lock()
        self.procs = {}              # addr -> Popen    guarded-by: _mu
        self.retired = {}            # addr -> exit code  guarded-by: _mu
        self._next_rank = 0          # guarded-by: _mu
        # ~20ms simulated service time per dispatch (max_batch 4 =>
        # ~200 req/s per replica): a spike's backlog lands in the
        # scheduler queue where srv_q sees it, not in socket overhead
        self._delay_ms = float(delay_ms)
        _, addr = self._spawn_child()
        self.router = FleetRouter([addr], digest_ttl_s=1.0).start()
        # short hysteresis/cooldown scaled to the drill's 0.25s ticks;
        # the production defaults ride FLAGS_fleet_* (README "Fleet")
        policy = AutoscalerPolicy(
            min_replicas=1, max_replicas=max_replicas, queue_high=3.0,
            idle_qps=0.5, up_ticks=2, down_ticks=4, cooldown_ticks=6,
            shed_after_ticks=2, shed_enabled=shed_enabled,
            initial_target=1)
        if backoff_s is not None:
            from paddle_tpu.flags import set_flags
            set_flags({"FLAGS_fleet_spawn_backoff_s": float(backoff_s)})
        try:
            self.scaler = FleetAutoscaler(self.router, self.spawn_fn,
                                          self.retire_fn, policy=policy,
                                          interval_s=interval_s)
        finally:
            if backoff_s is not None:
                set_flags({"FLAGS_fleet_spawn_backoff_s": 10.0})

    def _spawn_child(self):
        with self._mu:
            rank = self._next_rank
            self._next_rank += 1
        proc, addr = _spawn("replica", ["--rank", rank,
                                        "--batch-delay-ms",
                                        self._delay_ms])
        with self._mu:
            self.procs[addr] = proc
        return proc, addr

    def spawn_fn(self):
        return self._spawn_child()[1]

    def retire_fn(self, addr):
        with self._mu:
            proc = self.procs.pop(addr, None)
        if proc is None or proc.poll() is not None:
            return
        proc.send_signal(signal.SIGTERM)        # drain, never a kill
        code = _wait_exit(proc, timeout_s=30.0)
        with self._mu:
            self.retired[addr] = code

    def live(self):
        return len(self.live_addrs())

    def live_addrs(self):
        return [a for a, r in self.router.replica_view().items()
                if r["state"] in ("up", "stale")]

    def kill_replica(self, addr):
        with self._mu:
            proc = self.procs.get(addr)
        if proc is not None:
            proc.kill()

    def calibrate_slo(self, factor=3.0):
        """Light load on the seed replica measures a baseline p99; the
        fleet SLO objective is ``factor``x that, so the spike breaches
        and light traffic recovers regardless of host speed.  ONE
        client: the baseline must be queue-free (pure service time +
        transport) — any queuing in the baseline inflates the objective
        toward the spike's own latency and the breach goes marginal.
        Returns (calibration load, objective ms)."""
        from paddle_tpu.serving.slo import BurnRateEvaluator, SLOTarget
        cal = OpenLoopLoad(self.router, n_clients=1,
                           think_s=0.01).start()
        time.sleep(1.5)
        cal.stop()
        base = cal.p99_ms()
        assert base > 0, "SLO calibration produced no latencies"
        thresh = max(factor * base, 5.0)
        # threshold 5.0: breach needs >=5% of the window over the
        # objective (a spike is ~100%), recovery tolerates up to 2.5%
        # stragglers (threshold * 0.5 hysteresis) — CPU-noise-proof
        self.router.slo = BurnRateEvaluator(
            {"*": SLOTarget(p99_ms=thresh)},
            fast_window_s=1.5, slow_window_s=3.0, threshold=5.0)
        return cal, thresh

    def close(self):
        self.scaler.stop()
        self.router.stop()
        with self._mu:
            procs = list(self.procs.values())
        _kill_all(procs)


def scenario_scale(full=False, inject_spawn=False):
    """Load spike -> EXACTLY one counted scale-up -> p99 recovers under
    the objective with zero failures; SIGKILL under load -> death repair
    restores the target; sustained idle -> exactly one drain-retire.
    ``inject_spawn`` fails the first spawn attempt: the controller backs
    off, keeps shedding engaged while the breach lasts, retries after
    the backoff, and never recounts the decision."""
    from paddle_tpu import monitor as M
    from paddle_tpu import resilience as R
    from paddle_tpu.flags import set_flags

    name = "scale+inject" if inject_spawn else "scale"
    ctr0 = _scale_totals()

    def delta(d, r):
        return _ctr(M.FLEET_SCALE_CTR, dir=d, reason=r) - ctr0[(d, r)]

    dead_rr0 = _ctr(M.FLEET_REROUTE_CTR, reason="dead")
    fault0 = _ctr(R._FAULT_CTR, site="autoscaler.spawn")
    if inject_spawn:
        set_flags({"FLAGS_fault_inject": "autoscaler.spawn:once"})
    rig = _ScaleRig(shed_enabled=inject_spawn,
                    backoff_s=1.0 if inject_spawn else None)
    loads = []
    try:
        cal, thresh = rig.calibrate_slo()
        loads.append(cal)
        rig.scaler.start()

        # -- phase 1: spike -> one scale-up, shed only while spawning --
        # 24 clients vs ~200 req/s of replica capacity: ~5 batches of
        # queue wait (p99 >> the 3x objective) and srv_q well over the
        # policy's queue_high — both halves of the scale-up gate hold
        # for as long as the spike runs
        spike = OpenLoopLoad(rig.router, n_clients=24, think_s=0.002,
                             shed_ok=inject_spawn).start()
        loads.append(spike)
        shed_seen = [False]

        def scaled_up():
            if rig.router.snapshot().get("shedding"):
                shed_seen[0] = True
            return rig.live() >= 2

        _wait_until(scaled_up, 120.0, f"[{name}] scale-up to 2 replicas")
        time.sleep(1.0)              # the new replica takes spike load
        spike.stop()
        assert delta("up", "burn_queue") == 1, \
            f"[{name}] scale-up not counter-exact: " \
            f"{delta('up', 'burn_queue'):.0f}"
        assert delta("up", "death") == 0 and delta("up", "oom") == 0, \
            f"[{name}] spurious up counts: {_scale_totals()}"
        if inject_spawn:
            faults = _ctr(R._FAULT_CTR, site="autoscaler.spawn") - fault0
            assert faults == 1, f"[{name}] injected ledger: {faults}"
            assert rig.scaler.status()["spawn_failures"] == 1
            assert shed_seen[0], \
                f"[{name}] shed never engaged while the spawn was " \
                "in flight / backing off"

        # -- recovery: breach clears, shed releases, p99 under SLO -----
        rec = OpenLoopLoad(rig.router, n_clients=4, think_s=0.01,
                           shed_ok=inject_spawn).start()
        loads.append(rec)

        def recovered():
            st = rig.router.slo.evaluate()
            return bool(st) and not any(v["breached"]
                                        for v in st.values())

        _wait_until(recovered, 30.0, f"[{name}] SLO breach recovery")
        rec.stop()
        # fresh window AFTER the breach cleared: rec's own p99 would
        # still carry the tail of the pre-recovery transient
        post = OpenLoopLoad(rig.router, n_clients=4, think_s=0.01,
                            shed_ok=inject_spawn).start()
        loads.append(post)
        time.sleep(1.5)              # post-recovery latency sample
        post.stop()
        p99_rec = post.p99_ms()
        assert p99_rec < thresh, \
            f"[{name}] p99 did not return under the objective: " \
            f"{p99_rec:.0f}ms >= {thresh:.0f}ms"
        if inject_spawn:
            assert not rig.router.snapshot()["shedding"], \
                f"[{name}] shed still engaged after recovery"

        # -- phase 2: SIGKILL under load -> death repair to target -----
        kill_load = OpenLoopLoad(rig.router, n_clients=6, think_s=0.005,
                                 shed_ok=inject_spawn).start()
        loads.append(kill_load)
        time.sleep(0.8)
        rig.kill_replica(rig.live_addrs()[0])
        _wait_until(lambda: delta("up", "death") == 1
                    and rig.live() >= 2,
                    120.0, f"[{name}] death repair back to target")
        time.sleep(1.0)
        kill_load.stop()
        deads = _ctr(M.FLEET_REROUTE_CTR, reason="dead") - dead_rr0
        assert deads >= 1, f"[{name}] no dead re-route was recorded"
        assert delta("up", "burn_queue") == 1, \
            f"[{name}] repair recounted the scale-up decision"

        # -- phase 3: sustained idle -> exactly one drain-retire -------
        _wait_until(lambda: delta("down", "idle") == 1
                    and rig.live() == 1,
                    60.0, f"[{name}] idle drain-retire")
        assert delta("down", "surplus") == 0, \
            f"[{name}] surplus flap: {_scale_totals()}"

        # live() drops the moment the router marks the victim draining;
        # the retire worker records its exit code only after the
        # SIGTERM'd child finishes draining — wait for the record
        def _retire_recorded():
            with rig._mu:
                return len(rig.retired) == 1

        _wait_until(_retire_recorded, 40.0,
                    f"[{name}] retired child exit record")
        with rig._mu:
            retired = dict(rig.retired)
        assert len(retired) == 1 and all(c == 0
                                         for c in retired.values()), \
            f"[{name}] retired replica dropped work: {retired}"

        # -- ledger + controller liveness ------------------------------
        total_done, total_errors, total_sheds = 0, [], 0
        for ld in loads:
            done, errors = ld.counts()
            total_done += done
            total_errors += errors
            with ld._mu:
                total_sheds += len(ld.sheds)
        assert not total_errors, \
            f"[{name}] client-visible failures: {total_errors[:5]} " \
            f"({len(total_errors)} total)"
        snap = rig.router.snapshot()
        assert snap["failed"] == 0, f"[{name}] router failures: {snap}"
        assert snap["completed"] == snap["admitted"] == total_done, \
            f"[{name}] ledger does not sum: admitted=" \
            f"{snap['admitted']} completed={snap['completed']} " \
            f"client-done={total_done}"
        assert snap["rejected"] == total_sheds, \
            f"[{name}] rejected={snap['rejected']} != " \
            f"sheds={total_sheds}"
        if not inject_spawn:
            assert total_sheds == 0, \
                f"[{name}] shed engaged without the flag"
        st = rig.scaler.status()
        assert st["target"] == 1 and st["size"] == 1, st
        ticks0 = st["ticks"]
        time.sleep(0.7)
        assert rig.scaler.status()["ticks"] > ticks0, \
            f"[{name}] controller loop died"
        print(f"fleet {name} OK: {total_done} requests 0 failed "
              f"({total_sheds} shed), 1 scale-up 1 death-repair "
              f"1 idle-retire (exit 0), p99 {p99_rec:.0f}ms < "
              f"SLO {thresh:.0f}ms")
    finally:
        if inject_spawn:
            set_flags({"FLAGS_fault_inject": ""})
        for ld in loads:
            ld.stop()
        rig.close()


def scenario_scale_failover():
    """Coordinator failover must not flap the autoscaler: with the
    controller attached to the WARM STANDBY's status plane, SIGKILL the
    primary — the standby promotes (epoch bump), its status snapshot
    carries the autoscaler section (the gangtop TGT/SIZE footer), the
    controller keeps ticking, and the scale-counter ledger is untouched
    across the failover."""
    from paddle_tpu.distributed.coordinator import GangCoordinator

    prim, prim_addr = _spawn("coordinator", ["--world", 1])
    standby = GangCoordinator(1, port=0, heartbeat_timeout_s=HB_TIMEOUT,
                              standby_of=prim_addr).start()
    # min == max == 1 pins the fleet static: any scale count is a flap
    rig = _ScaleRig(max_replicas=1)
    rig.scaler.attach_to(standby)
    rig.scaler.start()
    load = OpenLoopLoad(rig.router, n_clients=4, think_s=0.01).start()
    try:
        time.sleep(1.0)
        ctr_before = _scale_totals()
        ticks0 = rig.scaler.status()["ticks"]
        prim.kill()                  # SIGKILL the primary coordinator
        _wait_until(lambda: standby.status_snapshot()
                    .get("coord_role") == "primary",
                    20.0, "[scale+coord] standby promotion")
        time.sleep(1.0)              # post-failover ticks + traffic
        load.stop()
        st = standby.status_snapshot()
        assert int(st.get("epoch", 0)) >= 1, \
            f"[scale+coord] promotion without epoch bump: {st}"
        asc = st.get("autoscaler")
        assert isinstance(asc, dict) and asc.get("target") == 1, \
            f"[scale+coord] autoscaler section missing from the " \
            f"promoted standby's status: {asc}"
        assert _scale_totals() == ctr_before, \
            f"[scale+coord] autoscaler flapped across the failover: " \
            f"{ctr_before} -> {_scale_totals()}"
        assert rig.scaler.status()["ticks"] > ticks0, \
            "[scale+coord] controller loop died across the failover"
        done, snap = _assert_ledger(rig.router, load, "scale+coord")
        # the gangtop footer renders from this exact status payload
        from gangtop import render
        txt = render(st)
        assert "fleet: TGT=1" in txt, txt
        print(f"fleet scale+coord OK: {done} requests 0 failed, "
              f"standby promoted epoch={st['epoch']}, controller "
              f"ticking, zero scale flaps, gangtop footer renders")
    finally:
        load.stop()
        standby.stop()
        rig.close()
        _kill_all([prim])


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--role", choices=("driver", "replica",
                                       "coordinator"), default="driver")
    ap.add_argument("--scenario",
                    choices=("drain", "kill", "coord", "scale"),
                    default=None, help="run one scenario (driver)")
    ap.add_argument("--full", action="store_true",
                    help="run the full kill matrix incl. fault "
                         "injection (slow)")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--rank", type=int, default=0)
    ap.add_argument("--world", type=int, default=1)
    ap.add_argument("--coord", default="")
    ap.add_argument("--manifest_dir", default="")
    ap.add_argument("--standby_of", default="")
    ap.add_argument("--batch-delay-ms", type=float, default=0.0,
                    help="replica role: simulated per-dispatch service "
                         "time (the scale drill's saturation knob)")
    args = ap.parse_args(argv)
    if args.role == "replica":
        return replica_main(args)
    if args.role == "coordinator":
        return coordinator_main(args)
    scenarios = {"drain": scenario_drain, "kill": scenario_kill,
                 "coord": scenario_coord, "scale": scenario_scale}
    if args.scenario:
        scenarios[args.scenario](full=args.full)
    else:
        scenario_drain(full=args.full)
        scenario_kill(full=args.full)
        scenario_coord(full=args.full)
        scenario_scale(full=args.full)
        if args.full:
            scenario_kill(full=True, inject_forward=True)
            scenario_coord(full=True, inject_frame=True)
            scenario_scale(full=True, inject_spawn=True)
            scenario_scale_failover()
    print("FLEET SMOKE PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
