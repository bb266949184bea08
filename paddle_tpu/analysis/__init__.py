"""Static program analysis (no direct reference counterpart — the
reference validates ProgramDesc graphs ad hoc at kernel launch; here the
whole class of launch-time defects is caught at ``compiler.optimize``
time, before anything is lowered).

- :mod:`paddle_tpu.analysis.verifier` — the program verifier: def-before-
  use, dangling feed/fetch targets, shape/dtype re-inference consistency,
  dead-op liveness, use-after-donate hazards on rw persistables, static
  int64 feed-wrap classification, and the per-rank collective-ordering
  fingerprint.  Whole-program: ``while``/``cond`` sub-blocks verify
  recursively in their enclosing scope context, and loop-body
  collectives fold into the fingerprint stamped with their block path.
  Runs on the ``framework.ir`` Graph, behind ``FLAGS_program_verify``
  (default on), cached on the source-program fingerprint so steady-state
  dispatch never re-verifies.
- :mod:`paddle_tpu.analysis.memory` — the static HBM peak-memory
  planner: interval liveness over the dependency-ordered Graph,
  donation- and alias-aware, producing per-program estimated peak bytes
  with a top-K per-op attribution table.  Feeds the verifier's
  ``memory_budget`` check, ``tests/test_hbm.py``'s planner
  estimate-vs-measured band, and ``tools/analyze.py``.
- :mod:`paddle_tpu.analysis.cost` — the analytic per-op flops/bytes
  model: 2·MAC matmul/conv formulas, grad-op inheritance, per-op-class
  roofline shares, cached on the program fingerprint.  Feeds the
  executor's live ``paddle_tpu_step_mfu`` gauge, the
  ``tests/test_device_attribution.py`` formula checks, the
  ``FLAGS_cost_crosscheck`` parity gate against XLA's own
  ``compiled.cost_analysis()``, and the fusion pass's candidate
  ranking.
- :mod:`paddle_tpu.analysis.numerics` — the numerics observability
  plane (``FLAGS_numerics``): in-graph tensor-health statistics packed
  into one per-step output (NaN/Inf sentinels, grad norms, update
  ratios, dynamic-range histograms), the anomaly engine (spike
  detection, profiler auto-capture, checkpoint quarantine), and the
  ``gnorm``/``nanf`` gang-digest keys — the value-domain counterpart of
  the cost/attribution plane.
- :mod:`paddle_tpu.analysis.device_profile` — MEASURED device-time
  attribution from the sampling profiler's captured windows: a
  chrome-trace + xplane.pb (dependency-free wire-format) parser joined
  to framework steps by the ``paddle_tpu.step`` ids, HLO/fusion kernel
  names mapped back to the cost-model op classes, per-step device time
  / idle fraction / per-class shares, measured MFU
  (``paddle_tpu_step_mfu_measured``, the ``mfu_m`` gang-digest key),
  and the measured-vs-analytic divergence table persisted as
  ``<window>/summary.json`` — the autotune search's objective oracle.
  NOT imported eagerly here: it is the profiler's lazy post-close
  dependency.
- :mod:`paddle_tpu.analysis.fusion` — the cost-guided training-safe
  graph fusion pass (``FLAGS_graph_fusion``): PDPattern-matched
  candidates (dense epilogues, embedding+layernorm), static legality
  analysis with grad-chain rewrite-or-reject and roofline ranking; runs
  in ``compiler.optimize``'s pass slot with the verifier before and
  after.
"""

from .comms import (  # noqa: F401
    CommsPlan, device_link_bandwidth, plan_comms,
)
from .cost import CostPlan, device_peak_flops, plan_cost  # noqa: F401
from .fusion import (  # noqa: F401
    FusionDecision, FusionReport, analyze_program, fuse_program,
)
from .memory import MemoryPlan, plan_memory  # noqa: F401
from .numerics import (  # noqa: F401
    NumericsEngine, NumericsFrame, StatsLayout, loss_fingerprint,
    plan_numerics, record_anomaly,
)
from .verifier import (  # noqa: F401
    CHECKS, Diagnostic, ProgramVerificationError, VerifyResult,
    clear_cache, collective_fingerprint, dynamic_int64_feeds,
    verify_or_raise, verify_program,
)

__all__ = [
    "CHECKS", "CommsPlan", "CostPlan", "Diagnostic", "FusionDecision",
    "FusionReport", "MemoryPlan", "NumericsEngine", "NumericsFrame",
    "ProgramVerificationError", "StatsLayout", "VerifyResult",
    "analyze_program", "clear_cache", "collective_fingerprint",
    "device_link_bandwidth", "device_peak_flops", "dynamic_int64_feeds",
    "fuse_program", "loss_fingerprint", "plan_comms", "plan_cost",
    "plan_memory", "plan_numerics", "record_anomaly", "verify_or_raise",
    "verify_program",
]
