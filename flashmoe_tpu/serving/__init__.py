"""Production serving subsystem: continuous batching, paged ragged KV
cache, and decode-shaped planner integration.

The training half of this framework reproduces the reference kernel
library and grows it into a trainer; this package is the first
subsystem on the INFERENCE half of the north star (ROADMAP item 1):

* :mod:`flashmoe_tpu.serving.kvcache` — a paged KV cache built on the
  same row-major ragged machinery as :mod:`flashmoe_tpu.ops.ragged`:
  block-table indirection, per-request lengths, deterministic page
  reuse on eviction, bucketed-length jit policy.
* :mod:`flashmoe_tpu.serving.engine` — a continuous-batching engine:
  per-step request admission/eviction/retirement over a fixed slot
  grid, deterministic under a seeded arrival trace (CI-testable on
  CPU), TTFT/TPOT/queue-depth/cache-occupancy through the flight
  recorder and ``serve.*`` decisions, TTFT/TPOT SLO budgets through
  the PR 8 watchdog.
* :mod:`flashmoe_tpu.serving.pools` — prefill/decode pool formation as
  heterogeneous inference-mode Decider groups (the reference's
  ``decider.cuh:177-268`` specialization); :mod:`flashmoe_tpu.fabric`
  composes these pools, a DCN-priced KV handoff, and a replica router
  into the disaggregated serving fabric (ROADMAP item 5).

CLI: ``python -m flashmoe_tpu.serving`` drives a seeded multi-request
drill and prints a JSON summary; ``python -m flashmoe_tpu.observe
--serving`` renders the serving report from the artifacts.  See
docs/SERVING.md.
"""

from flashmoe_tpu.serving.engine import (  # noqa: F401
    Request, ServeConfig, ServingEngine,
)
from flashmoe_tpu.serving.kvcache import (  # noqa: F401
    PagedKVCache, PagePool, SCRATCH_PAGE, ShardedPagePool,
    init_paged_cache,
)
from flashmoe_tpu.serving.pools import (  # noqa: F401
    PoolPlan, plan_serving_pools,
)
