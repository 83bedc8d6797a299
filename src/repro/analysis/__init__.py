"""Static analysis over traced jaxprs and Pallas kernels (offload-lint).

The paper's pipeline *starts* with static code analysis: loop statements are
parsed and classified before any GA measurement narrows them further. This
package is that stage for the jax_pallas port, in three layers:

* :mod:`repro.analysis.jaxpr_walk` — traverse ``ClosedJaxpr``s (including
  jit/scan/while/cond sub-jaxprs), classify every equation and derive
  per-region FLOPs, HBM-byte proxies, arithmetic intensity and trip counts —
  the jaxpr analogue of the paper's Clang loop parse.
* :mod:`repro.analysis.offload_lint` / :mod:`repro.analysis.kernel_lint` —
  findings with severity and stable IDs for serving-hot-path hazards
  (host syncs, un-donated decode state, f32 promotions, retrace hazards)
  and for Pallas kernel call sites (grid coverage, out-of-bounds block
  indexing, memory-space annotations).
* :mod:`repro.analysis.screen` — the static pre-screen ``search_fleet``
  runs before measuring: statically-dominated / resource-infeasible /
  below-intensity-floor cells never reach the GA's verification
  environment, and the measurements avoided are reported.
* :mod:`repro.analysis.concurrency` — the same read-before-run philosophy
  turned on the runtime itself: an AST race/deadlock lint (shared-state map
  from thread entry points, lock-discipline inference, lock-ordering
  cycles, blocking-under-lock) that certifies the concurrent fleet
  executor's single-writer contracts before the threads run.

``tools/offload_lint.py`` and ``tools/race_lint.py`` are the CLI + CI
gates over the lint layers; ``benchmarks/analysis_bench.py`` pins the
screen's pruning rate and ``benchmarks/concurrency_bench.py`` the
executor's identity + speedup.
"""
from repro.analysis.jaxpr_walk import (  # noqa: F401
    EqnStats, RegionReport, classify_primitive, trace_and_walk, walk_closed,
)
from repro.analysis.offload_lint import (  # noqa: F401
    Finding, lint_decode_family, lint_jaxpr_hazards, lint_model_families,
)
from repro.analysis.kernel_lint import (  # noqa: F401
    CapturedCall, capture_pallas_calls, lint_captured, lint_kernel_families,
)
from repro.analysis.screen import (  # noqa: F401
    CellStatics, ScreenPolicy, ScreenReport, screen_cells,
)
from repro.analysis.concurrency import (  # noqa: F401
    ConcurrencyReport, SharedAttr, lint_runtime, lint_scan, scan_paths,
    scan_source,
)
