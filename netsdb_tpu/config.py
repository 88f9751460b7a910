"""Framework configuration.

TPU-native analogue of the reference ``Configuration`` object
(``src/conf/headers/Configuration.h:22-71``): where netsDB sizes 64 MB
shared-memory pages, shuffle page sizes and thread counts, we size tensor
blocks (the sharding granularity), host page-store pages, and the device
mesh. Unlike the reference's argv-populated singleton, this is a plain
dataclass passed explicitly.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional, Tuple


@dataclasses.dataclass
class Configuration:
    """Global knobs; defaults chosen for TPU v5e.

    ``default_block_shape`` plays the role of netsDB's matrix block dims
    (reference tests default to 100x100 or 1000x1000 blocks,
    ``src/tests/source/FFTest.cc``); 512 is MXU/tiling friendly
    (multiple of 128 lanes / 8 sublanes).

    ``page_size_bytes`` mirrors ``Configuration::getPageSize`` (64 MB
    default) for the host-side page store.
    """

    # --- tensor blocking ---
    default_block_shape: Tuple[int, int] = (512, 512)
    # --- dtypes: MXU prefers bfloat16 inputs, f32 accumulation ---
    compute_dtype: str = "bfloat16"
    accum_dtype: str = "float32"
    storage_dtype: str = "float32"
    # --- host page store (native runtime) ---
    page_size_bytes: int = 64 * 1024 * 1024
    shared_mem_bytes: int = 4 * 1024 * 1024 * 1024
    # arena cap for PAGED sets (create_set(storage="paged")); None =
    # shared_mem_bytes. Separate knob because tests cap the page pool
    # tightly (forcing spills) while host sets stay uncapped.
    page_pool_bytes: Optional[int] = None
    # --- directories (reference: Configuration rootDir/catalog dirs) ---
    root_dir: str = dataclasses.field(
        default_factory=lambda: os.environ.get("NETSDB_TPU_HOME", "/tmp/netsdb_tpu")
    )
    # --- mesh defaults (data x model), overridden by parallel.mesh helpers ---
    mesh_shape: Optional[Tuple[int, ...]] = None
    mesh_axis_names: Tuple[str, ...] = ("data", "model")
    # --- out-of-core staging pipeline (plan/staging.py) ---
    # host page read-ahead depth for every block/chunk stream (the
    # PageCircularBuffer between the arena reader and the consumer);
    # 0 = synchronous reads. Replaces the executor's old hardwired
    # prefetch=0 call sites.
    stream_prefetch_pages: int = 2
    # device staging double-buffer depth: how many blocks ahead the
    # background thread runs jax.device_put (with the set's sharding)
    # of the consumer's fold step; 0 = synchronous device_put.
    stage_depth: int = 2
    # pad streamed row chunks up to the fixed bucket ladder
    # (plan/staging.bucket_rows: powers of two and 1.5x powers of two)
    # so ragged tails / differing ingest sizes reuse one compiled step
    # per bucket instead of compiling per distinct shape. Padded rows
    # ride the validity mask; False restores exact-shape padding.
    shape_bucketing: bool = True
    # buckets per octave in the shape ladder: 2 (default — {2^k,
    # 3*2^(k-1)}, <50% pad worst case) or 4 (2^(k-1)*{1.25,1.5,1.75}
    # rungs added — <25% pad at twice the compiles per octave).
    bucket_density: int = 2
    # --- fusion-aware plan compilation (plan/fusion.py) ---
    # master switch for the region mapper: on, the streamed executor
    # compiles maximal traceable resident subgraphs as ONE XLA program
    # per region (replacing per-node jit entries) and fuses streamed
    # folds' rowwise pre-chains / traceable epilogues into the fold's
    # compiled loop. Off byte-for-byte restores the per-node paths
    # (same jit-cache keys, trace counts and EXPLAIN shape) — the safe
    # rollback the acceptance gate pins.
    plan_fusion: bool = True
    # smallest node count worth compiling as one spine region (a
    # 1-node "region" is exactly today's per-node jit; floor 2)
    fusion_min_region: int = 2
    # cost feed for fusion decisions: "ledger" reads the per-(job,
    # node-label) OperatorLedger means (wall vs device gap = dispatch
    # overhead, retrace rates veto churn-prone labels), falling back
    # to a static estimate for never-seen labels; "static" forces the
    # fallback everywhere (cold daemons, deterministic tests)
    fusion_cost_source: str = "ledger"
    # region partitioner: "optimal" solves each maximal fusable run
    # exactly (DP over the region lattice — the runs are
    # topo-contiguous and convex, so contiguous-segment DP IS the
    # exact solution) under the staged-bytes budget below, splitting
    # an over-budget region at its cheapest edge; "greedy" restores
    # the PR 10 flush-the-whole-run mapper byte-for-byte (same region
    # ids, fingerprints, jit keys, counters) — the rollback arm the
    # A/B advisor compares against.
    fusion_mapper: str = "optimal"
    # HBM/pin byte budget one fused region's staged inputs may occupy
    # (cost model: per-label ledger means of bytes_in/stage.bytes,
    # static per-node fallback for cold labels). A run whose single-
    # region staging estimate exceeds this SPLITS at the cheapest
    # edge (fusion.splits ticks) instead of falling back per-node.
    # 0 = unbounded (the default — budget pressure is an operator/
    # TPU-rig decision, not something a CPU container can size).
    fusion_stage_budget_bytes: int = 0
    # --- cross-query device-resident set cache (storage/devcache.py) ---
    # byte budget for placed set blocks kept DEVICE-RESIDENT across
    # queries and serve requests (the buffer-pool role: the second
    # query over a hot set performs zero host->device transfers).
    # Entries key on (db, set, version, bucket, sharding); every write
    # path bumps the set version, so the cache can never serve stale
    # blocks. 0 disables. LRU-evicted under the budget.
    device_cache_bytes: int = 256 * 1024 * 1024
    # block-granular PARTIAL-RUN caching (the netsDB pin-per-page
    # discipline): entries install per block under (scope, kind,
    # bucket, sharding, block_range) as they stream — partial
    # consumption caches the consumed prefix — and lookups STITCH
    # contiguous cached ranges into the staged stream (cached ranges
    # serve from HBM with zero arena reads, gaps fall through to the
    # host-prefetch→upload pipeline). Invalidation is per-page dirty
    # ranges (SetStore._touch): an append drops only entries
    # intersecting the appended tail, so a huge set's warm prefix
    # survives small writes. False restores the whole-run
    # version-keyed behavior byte-for-byte (same keys, counters,
    # EXPLAIN — the rollback contract pinned by test).
    device_cache_partial: bool = True
    # pinnable hot-prefix budget (bytes, partial mode only): a set's
    # HEAD blocks — the contiguous prefix from row 0, in install
    # order — are marked pinned until this global budget is spent;
    # pinned entries are skipped by LRU eviction (dirty-range
    # invalidation still drops them). 0 disables pinning.
    device_cache_pin_bytes: int = 0
    # bound on the per-set dirty-range log (SetStore._touch): beyond
    # this many un-collapsed ranges the log folds to whole-scope (a
    # pathological writer degrades to today's invalidate-everything,
    # never to unbounded memory).
    device_cache_dirty_log: int = 64
    # --- distributed linear algebra (parallel/summa.py + reshard.py) ---
    # route streamed matmuls over paged operands through the
    # SUMMA-style distributed engine when >1 device is visible: each
    # mesh participant stages ONLY its own panel of the operands
    # (1/N of the bytes per host) and one compiled round program
    # broadcasts B panels per step over the mesh axis, accumulating
    # C tiles in place (arxiv 2112.09017). Off (default) keeps the
    # single-device block stream byte-for-byte.
    distributed_matmul: bool = False
    # participants for the SUMMA mesh: None = every visible device;
    # N caps it at the first N devices (the tier-1 virtual mesh tests
    # pin 4 of the suite's 8 host-platform devices)
    summa_participants: Optional[int] = None
    # 2-d processor grid for SUMMA ("PRxPC", e.g. "2x2", or a (pr, pc)
    # pair): operands whose BOTH dims exceed one host tile over the
    # full grid — each device stages 1/(pr*pc) of A AND of B, with
    # dual masked-psum broadcasts per step (arxiv 2112.09017 §III).
    # None (default) keeps the 1-d row-dealt mesh. A grid that does
    # not fit the visible device set falls back to 1-d; cached device
    # blocks move between the layouts via parallel/reshard.py.
    summa_grid: Optional[str] = None
    # derive the hot-prefix pin budget AUTOMATICALLY from the
    # attribution ledger's hot-set table on the scheduler-feedback
    # cadence (serve/sched/feedback.pin_budget — pinned formula),
    # when device_cache_pin_bytes is unset (0). The devcache stats
    # section annotates the active budget with "pin_auto": true.
    device_cache_pin_auto: bool = False
    # donate fold-step accumulators to XLA (donate_argnums on arg 0) so
    # per-block state updates reuse the same HBM buffer. None = auto:
    # on for backends that implement donation (TPU/GPU), off for CPU.
    donate_fold_buffers: Optional[bool] = None
    # --- observability (netsdb_tpu/obs/) ---
    # master switch for query-scoped tracing: on, every serve request
    # carrying a query id records a span profile (the -DPROFILING spans,
    # structured); off, span calls take the one-check fast path and
    # GET_TRACE returns empty. Metrics counters stay live either way
    # (they are integers, not allocations).
    obs_enabled: bool = True
    # completed query profiles retained for GET_TRACE (a bounded ring —
    # a year-long daemon holds exactly this many profiles)
    obs_trace_ring: int = 64
    # per-histogram retained samples in the metrics registry (exact
    # count/total/max are kept forever; quantiles come from the last N)
    obs_hist_samples: int = 512
    # 1-in-N query-id minting (obs.sample_qid): 1 traces every query
    # (the PR 5 behavior); N>1 mints a qid — and therefore pays span
    # recording, PUT_TRACE shipping and the optional device profile —
    # for one request in N, so high-QPS serving traces at bounded cost
    obs_trace_sample: int = 1
    # queries whose trace total exceeds this many seconds persist their
    # FULL profile to the bounded on-disk slowlog ring
    # (<root>/slowlog/, obs/slowlog.py — survives restarts); 0/None
    # disables
    obs_slow_query_s: Optional[float] = 5.0
    # slowlog files retained (oldest pruned beyond this)
    obs_slowlog_entries: int = 64
    # opt-in per-query jax.profiler sessions: a traced serve request
    # captures a REAL device profile into <dir>/<qid> (one session at a
    # time; concurrent traced queries skip, never queue). None = off.
    obs_device_profile_dir: Optional[str] = None
    # per-operator plan profiling (obs/operators.py): on, every TRACED
    # query additionally records an EXPLAIN ANALYZE tree (per-node
    # wall/device time, rows, chunk + cache/compile counters) into its
    # profile and the cross-query operator ledger; off, only explicit
    # EXECUTE(explain=True) requests record. Cost rides the trace
    # sampling knob.
    obs_explain: bool = True
    # continuous telemetry history (obs/history.py): the daemon
    # snapshots the registry's numeric surface every
    # obs_history_interval_s seconds into a ring of obs_history_len
    # readings (bounded: ring length x snapshot size), from which
    # GET_METRICS/`cli obs --top` derive rates (QPS, staged MB/s,
    # hit-rate trends). interval <= 0 or len < 2 disables the thread.
    obs_history_interval_s: float = 5.0
    obs_history_len: int = 120
    # --- serve-side query scheduler (netsdb_tpu/serve/sched/) ---
    # lane name -> weight for the weighted-deficit admission policy
    # (serve/sched/queue.py). Lanes not listed here get weight 1.0 on
    # first use. The daemon keys lanes by the frame's LANE_KEY hint,
    # falling back to its CLIENT_ID_KEY identity — per-client lanes
    # with zero client changes. None = every lane weight 1 (pure FIFO
    # fairness with aging).
    sched_lanes: Optional[Dict[str, float]] = None
    # max requests QUEUED per lane before the typed LaneSaturated
    # rejection (distinct from AdmissionFull: "this tenant is over its
    # share", not "the daemon is drowning"); 0 = unbounded lanes
    sched_lane_quota: int = 0
    # anti-starvation aging: every N grants, the lane whose head
    # waiter has waited longest is served regardless of weights — a
    # saturated low-priority lane admits within a bounded number of
    # high-priority admissions. 0 disables aging (pure deficit).
    sched_aging_every: int = 8
    # collapse byte-identical idempotent EXECUTE frames into ONE
    # execution fanned out to all waiters (serve/sched/coalesce.py);
    # each waiter keeps its own qid/trace/idempotency attribution
    sched_coalesce: bool = True
    # completed-fingerprint retention window (serve/sched/coalesce.py):
    # a byte-identical idempotent EXECUTE arriving within this many
    # seconds AFTER its coalesce leader finished still hits — the
    # retained reply is served under the late waiter's own qid/token
    # (sched.coalesce_late_hits). Staleness is bounded by the TTL (the
    # same window a client retry of a just-completed request would
    # observe). Default 0 = OFF: retention dedupes DISTINCT back-to-
    # back identical queries, not just concurrent ones — a visible
    # freshness trade the operator opts into per deployment (thundering
    # retry herds, dashboard fan-out), not a universal default.
    sched_coalesce_done_ttl_s: float = 0.0
    # completed-fingerprint entries retained (oldest evicted beyond
    # this — replies can be large, the bound is entries not bytes)
    sched_coalesce_done_max: int = 32
    # cache-aware hot-set admission (serve/sched/policy.py): when a
    # cold hot-set installer is already streaming, sibling queries on
    # the same placed sets queue behind it and wake into the warm
    # device cache instead of racing cold streams through the arena
    sched_affinity: bool = True
    # bound on how long an affinity sibling waits for the installer
    # before proceeding cold anyway (correctness never depends on the
    # wait — it is purely a thrash-avoidance window)
    sched_affinity_wait_s: float = 30.0
    # --- sharded worker pool (serve/placement.py + serve/shard.py) ---
    # byte bound on the leader's handoff buffers: ingest routed to a
    # DEGRADED shard slot buffers at the leader (typed retryable
    # refusal beyond the bound) and drains — only those pages — when
    # the shard readmits. The shard-scoped resync's memory ceiling.
    shard_handoff_bytes: int = 256 * 1024 * 1024
    # --- live shard rebalancing (serve/rebalance.py) ---
    # master switch for the self-rebalancing placement loop: on, the
    # leader watches per-shard load on the sched-feedback cadence (the
    # attribution ledger + shard COLLECT_STATS fan-out feed the pinned
    # skew formula), and sustained imbalance — or the pool growing/
    # shrinking — emits a bounded slot-move plan executed over the
    # RESHARD sub-protocol: copy while the source keeps serving, seal,
    # drain the tail, commit one epoch bump (old-epoch frames get the
    # typed retryable PlacementStale), drop the source copy. Off
    # (default), slots stay frozen at create_set — the PR 13 behavior,
    # byte-identical.
    rebalance: bool = False
    # max-shard-heat / mean-shard-heat ratio beyond which the detector
    # counts a window as skewed (must exceed 1.0 — a ratio of 1 is
    # perfect balance and would move data forever)
    rebalance_skew_ratio: float = 2.0
    # consecutive skewed feedback windows required before the planner
    # emits moves (pool growth/shrink bypasses this — new capacity
    # absorbs load immediately, not rebalance_windows cadences later)
    rebalance_windows: int = 3
    # byte bound on one planning round's moves: the planner stops
    # adding slot moves once their estimated bytes exceed this, so a
    # rebalance campaign trickles instead of saturating the data
    # plane. 0 = unbounded rounds.
    rebalance_max_bytes_per_round: int = 64 * 1024 * 1024
    # --- multi-host HA (serve/ha.py + storage/mutlog.py) ---
    # how long a follower must see EVERY earlier succession peer
    # unreachable before promoting itself leader under a new term.
    # Also the client's worst-case election window: a NotLeader
    # rejection with no leader address backs off within this bound.
    # The chaos tests shrink it to fractions of a second; production
    # wants it comfortably above one heartbeat_timeout_s.
    ha_election_timeout_s: float = 5.0
    # durable mutation log (storage/mutlog.py) under <root_dir>/mutlog:
    # on, the leader appends every mirrored frame on the mirror path
    # (log-replay resync for readmitted followers instead of a whole-
    # store snapshot) and the degraded-slot handoff buffer spills its
    # batches + drain tombstones (buffered ingest survives a leader
    # RESTART; the placement map persists alongside). Off (default),
    # resync falls back to the PR 2 snapshot stream and the handoff
    # buffer is memory-only — the pre-HA behavior, byte-identical.
    ha_mutlog: bool = False
    # --- scheduler feedback loop (serve/sched/) ---
    # seed lane weights (and per-lane quotas, when sched_lane_quota is
    # set) from observed behavior instead of the static sched_lanes
    # table: the per-(client, set) attribution ledger supplies each
    # lane's request/chunk/staged-byte volumes, the OperatorLedger's
    # cost rows supply the seconds-per-chunk conversion, and lanes
    # whose historical cost-per-request is LIGHT earn proportionally
    # more weight (clamped 0.25x-4x; the documented formula in
    # serve/sched/feedback.py, pinned by test). Re-seeded every
    # sched_feedback_every admissions. Opt-in: static lanes stay the
    # default.
    sched_feedback: bool = False
    sched_feedback_every: int = 64
    # SLO burn-rate load shedding (serve/sched/feedback.py): when an
    # obs/slo.py objective breaches on ALL windows, the scheduler
    # temporarily halves the heaviest non-reserved lane's quota
    # (pinned formula: quota × SHED_FACTOR, floored at 1) and ticks
    # ``sched.shed_events``; the override lifts on the first breach-
    # free check. Checked on the feedback cadence
    # (sched_feedback_every admissions). Opt-in; needs a configured
    # sched_lane_quota to have any quota to halve.
    sched_slo_shed: bool = False
    # --- stateful interactive serving (serve/sessions.py) ---
    # idle TTL for an open decode session: state untouched for this
    # long is evicted from the devcache (spilling to the host arena)
    # and, past a second TTL window, dropped from the table entirely.
    # Chaos tests shrink it to fractions of a second.
    session_ttl_s: float = 600.0
    # per-session cap on resident state bytes (recurrent h/c vectors,
    # KV cache pages). SESSION_OPEN rejects a model whose per-session
    # state would exceed it — the admission guard that keeps one fat
    # session from evicting everyone else's working set. 0 = uncapped.
    session_state_bytes: int = 16 * 1024 * 1024
    # max concurrent sessions coalesced into ONE padded decode step
    # program (the batched GENERATE path). Batch sizes quantize onto
    # the bucket_rows ladder, so churn between 1..decode_batch_max
    # live sessions never retraces.
    decode_batch_max: int = 8
    # multi-model residency dedup (dedup/ package): on, model-set
    # ingest through models/decode.py fingerprints weight pages with
    # dedup.detector and identical pages across fine-tuned model sets
    # install ONCE under a shared mapping — N near-identical models
    # resident for ~1 model's bytes + deltas. Attribution still
    # charges each client its exact share (shared pages split by
    # refcount). Off (default), every model's pages install privately.
    model_dedup: bool = False
    # --- concurrency correctness (netsdb_tpu/analysis/ + utils/locks) ---
    # lockdep-style runtime lock-order witness: on, every TrackedLock/
    # named-RWLock acquisition records rank edges (held -> acquired)
    # into one bounded process graph and flags cycles — potential
    # AB/BA deadlocks that never fired. The tier-1 suite enables it via
    # conftest; production defaults off (disabled cost: one global
    # read + is-None check per acquisition).
    lock_witness: bool = False
    # --- execution ---
    num_threads: int = 4  # host-side IO/pipeline threads (not device parallelism)
    enable_compression: bool = True  # host spill compression (ref -DENABLE_COMPRESSION)
    log_level: str = "WARNING"

    def __post_init__(self) -> None:
        if self.bucket_density not in (2, 4):
            raise ValueError(f"bucket_density must be 2 or 4, got "
                             f"{self.bucket_density!r}")
        if self.obs_trace_sample < 1:
            raise ValueError(f"obs_trace_sample must be >= 1, got "
                             f"{self.obs_trace_sample!r}")
        if self.fusion_cost_source not in ("ledger", "static"):
            raise ValueError(f"fusion_cost_source must be 'ledger' or "
                             f"'static', got "
                             f"{self.fusion_cost_source!r}")
        if self.fusion_mapper not in ("optimal", "greedy"):
            raise ValueError(f"fusion_mapper must be 'optimal' or "
                             f"'greedy', got {self.fusion_mapper!r}")
        if self.fusion_stage_budget_bytes < 0:
            raise ValueError(f"fusion_stage_budget_bytes must be >= 0, "
                             f"got {self.fusion_stage_budget_bytes!r}")
        if self.rebalance_skew_ratio <= 1.0:
            raise ValueError(f"rebalance_skew_ratio must be > 1.0, got "
                             f"{self.rebalance_skew_ratio!r}")
        if self.rebalance_windows < 1:
            raise ValueError(f"rebalance_windows must be >= 1, got "
                             f"{self.rebalance_windows!r}")
        if self.rebalance_max_bytes_per_round < 0:
            raise ValueError(f"rebalance_max_bytes_per_round must be "
                             f">= 0, got "
                             f"{self.rebalance_max_bytes_per_round!r}")
        if self.session_ttl_s <= 0:
            raise ValueError(f"session_ttl_s must be > 0, got "
                             f"{self.session_ttl_s!r}")
        if self.session_state_bytes < 0:
            raise ValueError(f"session_state_bytes must be >= 0, got "
                             f"{self.session_state_bytes!r}")
        if self.decode_batch_max < 1:
            raise ValueError(f"decode_batch_max must be >= 1, got "
                             f"{self.decode_batch_max!r}")

    @property
    def catalog_path(self) -> str:
        return os.path.join(self.root_dir, "catalog.sqlite")

    @property
    def data_dir(self) -> str:
        return os.path.join(self.root_dir, "data")

    def ensure_dirs(self) -> None:
        os.makedirs(self.data_dir, exist_ok=True)


#: the ONE compile-cache location when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: a fixed, git-ignored directory inside the checkout. It must not
#: depend on ``root_dir``, a pid or a temp name — a daemon started under
#: a fresh root would otherwise never hit it, and a cold FF + decode +
#: q01/q06 compile is most of a cold start.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_compile_cache")


def enable_compilation_cache() -> str:
    """Turn on jax's persistent compilation cache (reference: the
    master's PreCompiledWorkload plan cache,
    ``src/queryPlanning/headers/PreCompiledWorkload.h`` — compiled XLA
    executables keyed by HLO hash, shared across processes, so a fresh
    process reaches steady state without a cold compile).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax's own reading of it
    stands and no directory is set in code; otherwise the cache lives at
    :data:`COMPILE_CACHE_DIR`. Idempotent. Returns the active
    directory."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(COMPILE_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    # cache everything: the queries this framework compiles are
    # worth persisting even when individually quick to build
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


DEFAULT_CONFIG = Configuration()
