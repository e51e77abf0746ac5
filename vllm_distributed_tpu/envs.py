"""Environment variable registry.

Mirrors the reference's vllm/envs.py (125 lazily-evaluated VLLM_* vars): every
framework knob that is not part of EngineArgs lives here, is lazily evaluated
at first access, and is documented in one place. Prefix is VDT_ (and we also
honor the corresponding VLLM_ spelling for drop-in compatibility where the
semantic matches).
"""

import os
from typing import Any, Callable

environment_variables: dict[str, Callable[[], Any]] = {
    # Logging level for the framework's logger tree (DEBUG/INFO/WARNING...).
    "VDT_LOGGING_LEVEL":
    lambda: os.getenv("VDT_LOGGING_LEVEL", os.getenv("VLLM_LOGGING_LEVEL", "INFO")).upper(),
    # Optional prefix prepended to every log line.
    "VDT_LOGGING_PREFIX":
    lambda: os.getenv("VDT_LOGGING_PREFIX", os.getenv("VLLM_LOGGING_PREFIX", "")),
    # Use the pure-XLA reference attention instead of the Pallas kernels
    # (debugging / CPU execution).
    "VDT_ATTENTION_BACKEND":
    lambda: os.getenv("VDT_ATTENTION_BACKEND", "auto"),  # auto|pallas|xla
    # MoE compute path: grouped ragged_dot dispatch (default) or the
    # all-expert einsum baseline (A/B + FLOP regression tests).
    "VDT_MOE_BACKEND":
    lambda: os.getenv("VDT_MOE_BACKEND", "ragged"),  # ragged|dense
    # Expert-parallel dispatch mechanism: "a2a" = token-sharded
    # all-to-all rows to expert-owner ranks (falls back automatically
    # when inapplicable, e.g. EPLB replicas or indivisible buckets);
    # "replicate" forces the replicate+psum path.
    "VDT_MOE_EP_MODE":
    lambda: os.getenv("VDT_MOE_EP_MODE", "a2a"),
    # Max KV pages a finished pull applies to the cache per engine step
    # (the donated scatter runs on the scheduling thread; chunking keeps
    # any single step's apply bounded so co-resident decode latency
    # doesn't spike while a large pull lands).
    "VDT_KV_APPLY_CHUNK_PAGES":
    lambda: max(1, int(os.getenv("VDT_KV_APPLY_CHUNK_PAGES", "64"))),
    # Precompile the full shape lattice at startup: "auto" = on for
    # accelerator platforms, off on CPU; "1"/"0" force.
    "VDT_PRECOMPILE":
    lambda: os.getenv("VDT_PRECOMPILE", "auto"),
    # Raise (instead of warn) if a serving step compiles a new XLA graph
    # after precompile warm-up (recompile-storm guard; used in tests).
    "VDT_ASSERT_NO_RECOMPILE":
    lambda: os.getenv("VDT_ASSERT_NO_RECOMPILE", "0") == "1",
    # Force the engine core into a subprocess regardless of config
    # (reference: VLLM_ENABLE_V1_MULTIPROCESSING).
    "VDT_ENABLE_MP_ENGINE":
    lambda: os.getenv("VDT_ENABLE_MP_ENGINE", "0") == "1",
    # Run Pallas kernels in interpret mode (CPU tests).
    "VDT_PALLAS_INTERPRET":
    lambda: os.getenv("VDT_PALLAS_INTERPRET", "0") == "1",
    # Fuse the per-layer KV-page write into the attention mega-kernel
    # (one pass over the cache per mixed step) when the layout permits;
    # "0" keeps the separate write-then-attend pair for debugging.
    "VDT_FUSED_KV_WRITE":
    lambda: os.getenv("VDT_FUSED_KV_WRITE", "1") == "1",
    # Fraction of HBM usable for weights+KV (analogue of gpu_memory_utilization
    # default source).
    "VDT_MEMORY_FRACTION":
    lambda: float(os.getenv("VDT_MEMORY_FRACTION", "0.9")),
    # RPC timeout (seconds) for engine-core client handshakes.
    "VDT_RPC_TIMEOUT":
    lambda: float(os.getenv("VDT_RPC_TIMEOUT", "600")),
    # Port for the ZMQ engine-core transport (0 = auto).
    "VDT_ENGINE_CORE_PORT":
    lambda: int(os.getenv("VDT_ENGINE_CORE_PORT", "0")),
    # API key for the OpenAI server ("" disables auth).
    "VDT_API_KEY":
    lambda: os.getenv("VDT_API_KEY", os.getenv("VLLM_API_KEY", "")),
    # Host IP override used for distributed bootstrap.
    "VDT_HOST_IP":
    lambda: os.getenv("VDT_HOST_IP", os.getenv("VLLM_HOST_IP", "")),
    # jax.profiler trace output directory for the profile RPC
    # (reference: VLLM_TORCH_PROFILER_DIR).
    "VDT_PROFILER_DIR":
    lambda: os.getenv("VDT_PROFILER_DIR", "/tmp/vdt_profile"),
    # Hardened profiler capture window: seconds after which an
    # unstopped jax.profiler trace is force-stopped by the engine core
    # (a wedged xprof client must never wedge serving; the
    # perf.capture_stall fault drill pins this).
    "VDT_PROFILE_MAX_S":
    lambda: float(os.getenv("VDT_PROFILE_MAX_S", "120")),
    # Directory where multi-host follower processes publish their
    # telemetry snapshots (shm-ring read side + device stats) for host
    # 0's stats plane to fold in; "" disables the export.
    "VDT_FOLLOWER_STATS_DIR":
    lambda: os.getenv("VDT_FOLLOWER_STATS_DIR", ""),
    # Request-lifecycle event timeline (metrics/events.py): per-request
    # phase attribution (queue/kv_pull/prefill/decode/stalls) recorded
    # at lifecycle transitions and stitched into child phase spans by
    # the tracer. "0" disables all recording (bench runs both legs to
    # bound the overhead). Read ONCE per component at construction.
    "VDT_REQUEST_TIMELINE":
    lambda: os.getenv("VDT_REQUEST_TIMELINE", "1") == "1",
    # Cascade (shared-prefix) attention on the XLA path: "1" enables the
    # detection + split; opt-in because it adds a second compiled
    # forward variant per shape bucket.
    "VDT_CASCADE_ATTENTION":
    lambda: os.getenv("VDT_CASCADE_ATTENTION", "0") == "1",
    # Page count of the dense shared phase (cascade fires only when the
    # batch-wide common prefix covers at least this many pages).
    "VDT_CASCADE_SHARED_PAGES":
    lambda: int(os.getenv("VDT_CASCADE_SHARED_PAGES", "4")),
    # Disable the usage-stats style telemetry (always disabled by default;
    # kept for CLI parity).
    "VDT_NO_USAGE_STATS":
    lambda: os.getenv("VDT_NO_USAGE_STATS", "1") == "1",
    # --- Cluster routing tier (engine/router.py) ------------------------
    # Prefix-affinity + SLO-aware replica placement for the DP front
    # end. "0" reverts DPEngineClient to the pure live-count round-robin
    # balancer (byte-identical to the pre-router behavior).
    "VDT_ROUTER":
    lambda: os.getenv("VDT_ROUTER", "1") == "1",
    # Freshness budget (seconds) for the per-replica stats snapshots the
    # router scores with. In-process replicas refresh synchronously on
    # the admission path once the TTL expires; subprocess replicas are
    # fed passively by the server's existing get_stats polls (/metrics,
    # admission KV sampler) — never a new channel.
    "VDT_ROUTER_STATS_TTL_S":
    lambda: float(os.getenv("VDT_ROUTER_STATS_TTL_S", "1.0")),
    # Staleness horizon: when EVERY replica's load snapshot is older
    # than this, the router degrades to pure least-loaded balancing
    # (affinity with blind load signals would herd session traffic onto
    # one replica).
    "VDT_ROUTER_STALE_S":
    lambda: float(os.getenv("VDT_ROUTER_STALE_S", "5.0")),
    # Max leading prompt pages hashed for the affinity score (bounds
    # per-admission hashing cost for very long prompts).
    "VDT_ROUTER_PREFIX_PAGES":
    lambda: max(1, int(os.getenv("VDT_ROUTER_PREFIX_PAGES", "64"))),
    # Per-replica bound on the prefix-residency index (LRU entries).
    "VDT_ROUTER_PREFIX_CAPACITY":
    lambda: max(16, int(os.getenv("VDT_ROUTER_PREFIX_CAPACITY", "8192"))),
    # Seconds a residency entry stays credible without being touched
    # (a replica under steady traffic has almost certainly recycled the
    # pages by then).
    "VDT_ROUTER_PREFIX_TTL_S":
    lambda: float(os.getenv("VDT_ROUTER_PREFIX_TTL_S", "600")),
    # Pressure (blended KV usage / queue score, 0..1) above which the
    # affinity home is overridden and the request spills to the
    # least-cost healthy replica.
    "VDT_ROUTER_SPILL_PRESSURE":
    lambda: float(os.getenv("VDT_ROUTER_SPILL_PRESSURE", "0.85")),
    # --- Disaggregated prefill/decode serving tier (engine/disagg.py) ---
    # Master switch: "1" splits a DP fleet (data_parallel_size > 1) into
    # a prefill pool (chunked-prefill producers, big token buckets) and
    # a decode pool (deep decode batches, pull consumers) with routed KV
    # handoff between them. "0" (default) keeps the monolithic DP
    # balancer byte-identical to the pre-disagg behavior.
    "VDT_DISAGG":
    lambda: os.getenv("VDT_DISAGG", "0") == "1",
    # Replicas assigned to the prefill pool (the first k DP ranks).
    # 0 = auto: half the fleet, at least 1, always leaving >= 1 decode
    # replica.
    "VDT_DISAGG_PREFILL_REPLICAS":
    lambda: max(0, int(os.getenv("VDT_DISAGG_PREFILL_REPLICAS", "0"))),
    # Decode-pool scheduler token budget (max_num_batched_tokens of the
    # decode replicas). Bounds both the decode wave depth and the
    # chunk size of the local re-prefill fallback, and therefore the
    # decode pool's precompiled token-bucket ladder. 0 = auto:
    # max(max_num_seqs, 2 * block_size), clipped to the parent budget.
    "VDT_DISAGG_DECODE_TOKENS":
    lambda: max(0, int(os.getenv("VDT_DISAGG_DECODE_TOKENS", "0"))),
    # Per-pool tensor-parallel degree (0 = inherit the parent config).
    # Asymmetric meshes work because the KV handoff rides the versioned
    # standard/latent wire formats, which re-slice on receipt.
    "VDT_DISAGG_PREFILL_TP":
    lambda: max(0, int(os.getenv("VDT_DISAGG_PREFILL_TP", "0"))),
    "VDT_DISAGG_DECODE_TP":
    lambda: max(0, int(os.getenv("VDT_DISAGG_DECODE_TP", "0"))),
    # --- Hierarchical KV/state memory (core/kv_tier.py) -----------------
    # Master switch: "1" gives the page pool a spill hierarchy — prefix
    # pages evicted from HBM demote to a bounded pinned-host-RAM pool
    # (T1), host-pool eviction demotes to disk page files (T2, the
    # shared_storage format + CRC + quantized codec under the same
    # content-addressed BlockHash keys), and WAITING requests whose
    # prefix lives in a tier promote it back before the forward. SSM
    # state-cache eviction likewise demotes snapshots to the checkpoint
    # journal instead of discarding. "0" (default) constructs no tier
    # state anywhere — byte-identical revert. Read at engine build.
    "VDT_KV_TIERING":
    lambda: os.getenv("VDT_KV_TIERING", "0") == "1",
    # T1 budget: MiB of host RAM the demoted-page pool may hold before
    # spilling its LRU pages to the disk tier (fractions allowed —
    # tiny-geometry tests/bench force spills with sub-MiB budgets).
    "VDT_KV_TIER_HOST_MB":
    lambda: max(0.001, float(os.getenv("VDT_KV_TIER_HOST_MB", "512"))),
    # T2 spill directory ("" disables the disk tier; host-pool eviction
    # then discards). Content-addressed page files — safe to share with
    # a shared_storage store or across replicas of the SAME model
    # (namespace discipline is the operator's, as with shared_storage).
    "VDT_KV_TIER_DIR":
    lambda: os.getenv("VDT_KV_TIER_DIR", ""),
    # T2 budget: MiB of spill files kept on disk (oldest evicted past
    # the budget; fractions allowed like the host budget).
    "VDT_KV_TIER_DISK_MB":
    lambda: max(0.001, float(os.getenv("VDT_KV_TIER_DISK_MB", "4096"))),
    # Demotion cap: pages gathered device->host per engine step. The
    # gather is dispatched pre-forward (its DMA overlaps the step);
    # evictions past the cap lose their demotion (counted) because the
    # new page owner overwrites the content this very step.
    "VDT_KV_TIER_DEMOTE_PAGES":
    lambda: max(1, int(os.getenv("VDT_KV_TIER_DEMOTE_PAGES", "64"))),
    # --- Elastic fleet controller (engine/fleet.py) ---------------------
    # Master switch: "1" hosts a FleetController next to the DP balancer
    # — closed-loop scale-out/in over the replica set, live prefill <->
    # decode pool re-splits, wedge detection, and the folded resurrection
    # probe (one actuator, one budget). "0" (default) constructs no
    # controller: no extra thread, no new RPCs, and the legacy periodic
    # resurrection probe runs byte-identical to the pre-fleet behavior.
    "VDT_FLEET":
    lambda: os.getenv("VDT_FLEET", "0") == "1",
    # Fleet-size floor/ceiling for scale decisions. MIN bounds scale-in
    # (never retire below it). MAX bounds scale-out; 0 = auto: the boot
    # data_parallel_size (scale-out then only refills retired slots —
    # growing past boot needs devices the operator must provision).
    "VDT_FLEET_MIN_REPLICAS":
    lambda: max(1, int(os.getenv("VDT_FLEET_MIN_REPLICAS", "1"))),
    "VDT_FLEET_MAX_REPLICAS":
    lambda: max(0, int(os.getenv("VDT_FLEET_MAX_REPLICAS", "0"))),
    # Seconds between control-loop evaluations (ticks ride the output
    # path next to the resurrection probe; no dedicated thread).
    "VDT_FLEET_TICK_S":
    lambda: max(0.0, float(os.getenv("VDT_FLEET_TICK_S", "1.0"))),
    # Occupancy watermarks (fleet-wide live slots / (active replicas *
    # max_num_seqs)): sustained occupancy above HIGH scales out, below
    # LOW scales in. HIGH/LOW must straddle to hysterese.
    "VDT_FLEET_HIGH_WATERMARK":
    lambda: float(os.getenv("VDT_FLEET_HIGH_WATERMARK", "0.85")),
    "VDT_FLEET_LOW_WATERMARK":
    lambda: float(os.getenv("VDT_FLEET_LOW_WATERMARK", "0.25")),
    # Consecutive ticks a watermark (or pool-imbalance) signal must hold
    # before the controller actuates — the hysteresis half of the
    # anti-thrash story (the action budget is the other half).
    "VDT_FLEET_EVAL_TICKS":
    lambda: max(1, int(os.getenv("VDT_FLEET_EVAL_TICKS", "3"))),
    # Per-replica stats snapshots older than this freeze all actuation
    # (counted in vdt:fleet_freezes_total{reason="stale_stats"}) — the
    # router stale_stats idiom: never reshape the fleet on blind signals.
    "VDT_FLEET_STALE_S":
    lambda: max(0.0, float(os.getenv("VDT_FLEET_STALE_S", "10"))),
    # A replica with live requests whose steps_dispatched counter has
    # not advanced for this long is WEDGED (alive-but-not-stepping): its
    # journaled requests migrate off and it is force-cycled through the
    # PR-2 restart budget. 0 disables wedge detection.
    "VDT_FLEET_WEDGE_S":
    lambda: max(0.0, float(os.getenv("VDT_FLEET_WEDGE_S", "30"))),
    # Drain deadline for a retiring/converting replica: past it, still-
    # unfinished requests journal-migrate as continuations (token-
    # identical under greedy) and the drain completes anyway.
    "VDT_FLEET_DRAIN_S":
    lambda: max(0.0, float(os.getenv("VDT_FLEET_DRAIN_S", "30"))),
    # Supervisor-style action budget: at most ACTIONS fleet actions
    # (scale-out/in, re-split, wedge cycle) per rolling WINDOW seconds;
    # past it actuation freezes (reason="budget") until the window
    # slides — an oscillating signal cannot thrash the fleet.
    "VDT_FLEET_ACTIONS":
    lambda: max(1, int(os.getenv("VDT_FLEET_ACTIONS", "6"))),
    "VDT_FLEET_ACTION_WINDOW_S":
    lambda: max(1.0, float(os.getenv("VDT_FLEET_ACTION_WINDOW_S",
                                     "300"))),
    # Live pool re-split trigger (VDT_DISAGG fleets): convert one
    # replica toward the pressured pool when its per-replica occupancy
    # exceeds the other pool's by this factor. 0 disables re-splits.
    "VDT_FLEET_RESPLIT_RATIO":
    lambda: max(0.0, float(os.getenv("VDT_FLEET_RESPLIT_RATIO", "3"))),
    # --- HA fleet control plane (engine/control_plane.py) ---------------
    # Master switch: "1" hoists the FleetController behind the DP
    # coordinator's lease/fence plane — every front-end hosts a standby
    # controller, exactly one holds the TTL lease and actuates, every
    # actuation carries the lease epoch and the coordinator rejects
    # stale-epoch commands (counted, never raised into serving), and
    # multi-step actions journal intents so a successor leader can
    # finish them. "0" (default) keeps the PR-16 in-process controller
    # byte-identical: no lease RPCs, no journal, no fencing.
    "VDT_FLEET_CONTROLLER":
    lambda: os.getenv("VDT_FLEET_CONTROLLER", "0") == "1",
    # Lease TTL in seconds (monotonic server clock). The leader renews
    # each tick; a standby takes over within TTL of leader death. Ticks
    # must run faster than the TTL or leadership flaps.
    "VDT_FLEET_LEASE_TTL_S":
    lambda: max(0.001, float(os.getenv("VDT_FLEET_LEASE_TTL_S", "10"))),
    # Actuation-journal directory ("" = auto: <VDT_KV_TIER_DIR>/
    # fleet_journal when the T2 spill namespace is configured, else a
    # per-fleet tempdir). Intent records are one JSON file per in-flight
    # multi-step action, written atomically before each rung; a newly
    # elected leader replays or aborts whatever it finds here.
    "VDT_FLEET_JOURNAL_DIR":
    lambda: os.getenv("VDT_FLEET_JOURNAL_DIR", ""),
    # The goodput signal: "1" folds per-tenant goodput (PR 13's SLO
    # scoring) into the scale-out/in decision — a goodput-starved fleet
    # scales out earlier and resists scale-in. "0" (default) decides on
    # occupancy alone.
    "VDT_FLEET_SIGNALS":
    lambda: os.getenv("VDT_FLEET_SIGNALS", "0") == "1",
    # A min per-tenant goodput below FLOOR counts as scale-out pressure
    # / vetoes scale-in. FLOOR <= 0 disables the term even with signals
    # on.
    "VDT_FLEET_GOODPUT_FLOOR":
    lambda: float(os.getenv("VDT_FLEET_GOODPUT_FLOOR", "0.5")),
    # --- SSM state cache (core/state_cache.py) --------------------------
    # First-class state checkpoint/restore for stateful (Mamba/Jamba)
    # models: prefix-style admission at snapshot boundaries, preemption
    # that parks state instead of recomputing, and O(1) crash-recovery
    # resume. "0" reverts wholesale to the pre-cache behavior (prefix
    # caching disabled for stateful models, preemption recomputes from
    # token 0, journal replay re-prefills the whole prompt).
    "VDT_SSM_STATE_CACHE":
    lambda: os.getenv("VDT_SSM_STATE_CACHE", "1") == "1",
    # Snapshot-pool slots (device rows per state array). 0 = auto:
    # max(2 * max_num_seqs, 8).
    "VDT_SSM_STATE_CACHE_SLOTS":
    lambda: max(0, int(os.getenv("VDT_SSM_STATE_CACHE_SLOTS", "0"))),
    # Checkpoint cadence in tokens (rounded up to a page multiple so
    # every snapshot boundary is also a block-hash boundary). Crash
    # recovery re-prefills at most this many tokens.
    "VDT_SSM_CKPT_INTERVAL":
    lambda: max(1, int(os.getenv("VDT_SSM_CKPT_INTERVAL", "256"))),
    # Host checkpoint-journal directory for crash recovery ("" keeps
    # snapshots device-only). Files use the shared_storage connector's
    # atomic tmp+rename discipline, one .npz per snapshot boundary.
    "VDT_SSM_CKPT_DIR":
    lambda: os.getenv("VDT_SSM_CKPT_DIR", ""),
    # Checkpoint-journal retention: files are content-addressed and
    # deliberately outlive their requests (they ARE the crash-recovery
    # tier), so a sweep on manager init and on sleep() bounds the
    # directory instead of per-request deletes. Max total MiB (oldest
    # files reclaimed first past the budget; 0 = unbounded) and max file
    # age in seconds (0 = no TTL). Files still referenced by an
    # unshipped journal write are never reclaimed.
    "VDT_SSM_CKPT_MAX_MB":
    lambda: max(0, int(os.getenv("VDT_SSM_CKPT_MAX_MB", "1024"))),
    "VDT_SSM_CKPT_TTL_S":
    lambda: max(0.0, float(os.getenv("VDT_SSM_CKPT_TTL_S", "604800"))),
    # --- TPLA: tensor-parallel latent attention (ops/mla.py) ------------
    # Shard the MLA (DeepSeek) latent KV cache over the TP axis (PAPERS.md
    # "TPLA"): each rank stores kv_lora_rank/TP of every latent row (the
    # rope k_pe sidecar stays replicated), so the per-rank latent pool is
    # ~1/TP the bytes and MLA concurrency scales ~TP-fold. Default on for
    # TP>1 MLA models; "0" reverts wholesale to the replicated layout
    # (byte-identical cache, head-sharded attention). Read at model load.
    "VDT_TPLA":
    lambda: os.getenv("VDT_TPLA", "1") == "1",
    # --- API admission control / overload protection -------------------
    # High watermark: concurrent admitted HTTP generation requests above
    # which the server sheds load with 429 + Retry-After. 0 disables
    # admission control entirely.
    "VDT_ADMISSION_HIGH_WATERMARK":
    lambda: int(os.getenv("VDT_ADMISSION_HIGH_WATERMARK", "256")),
    # Low watermark (hysteresis): once shedding starts it continues
    # until depth falls to this level. 0 = derive as 3/4 of the high
    # watermark.
    "VDT_ADMISSION_LOW_WATERMARK":
    lambda: int(os.getenv("VDT_ADMISSION_LOW_WATERMARK", "0")),
    # Free-KV-page pressure shed: fraction of KV pages in use above
    # which admission sheds (sampled from engine stats at most twice a
    # second). 0.0 disables the KV-pressure trigger.
    "VDT_ADMISSION_KV_HIGH":
    lambda: float(os.getenv("VDT_ADMISSION_KV_HIGH", "0")),
    # Weighted per-class shedding: fraction of the high/low watermarks
    # at which BEST-EFFORT traffic (request priority > 0) sheds, so
    # overload evicts best-effort requests before interactive ones.
    # 1.0 disables the distinction (all classes share one watermark).
    "VDT_ADMISSION_BEST_EFFORT_FRAC":
    lambda: min(1.0, max(0.05, float(
        os.getenv("VDT_ADMISSION_BEST_EFFORT_FRAC", "0.75")))),
    # Retry-After seconds advertised on shed (429) and drain (503).
    "VDT_RETRY_AFTER_S":
    lambda: max(1, int(os.getenv("VDT_RETRY_AFTER_S", "1"))),
    # Per-request wall-clock deadline (seconds) for generation
    # endpoints; overdue requests abort through the engine's abort path
    # and answer 408. 0 disables; a request body's "timeout_s" field
    # overrides per call.
    "VDT_REQUEST_TIMEOUT_S":
    lambda: float(os.getenv("VDT_REQUEST_TIMEOUT_S", "0")),
    # SIGTERM drain deadline: seconds to let in-flight requests finish
    # after admission stops before the server exits anyway.
    "VDT_DRAIN_TIMEOUT_S":
    lambda: float(os.getenv("VDT_DRAIN_TIMEOUT_S", "30")),
    # --- Per-tenant QoS (core/sched/qos.py) ----------------------------
    # Scheduler-level execution fairness: "1" turns on deficit-round-
    # robin weighted fair queueing over tenants (granted tokens draw
    # down per-tenant deficit counters; chunked-prefill grants clip to
    # them), soft per-tenant KV page quotas with quota-aware preemption
    # (cause "quota"), and the vdt:tenant_* metric families. "0" (the
    # default) constructs no QoS state — scheduling is byte-identical
    # to the pre-QoS behavior. Read once at scheduler construction.
    "VDT_QOS":
    lambda: os.getenv("VDT_QOS", "0") == "1",
    # Weight spec: comma list of "name:weight" where name is a tenant
    # id or a class key ("interactive"/"best_effort"/"default").
    # Unlisted tenants take their priority class's weight, then
    # "default", then 1.0 (equal shares).
    "VDT_QOS_WEIGHTS":
    lambda: os.getenv("VDT_QOS_WEIGHTS", ""),
    # Soft per-tenant KV quota as a fraction of the page pool. Free
    # until pressure: enforced only when the pool is pressured
    # (admission gating at >= 0.9 usage; preemption victim choice on
    # allocation failure). Values outside (0, 1) disable quotas.
    "VDT_QOS_KV_QUOTA_FRAC":
    lambda: float(os.getenv("VDT_QOS_KV_QUOTA_FRAC", "0.5")),
    # Cardinality bound for the vdt:tenant_* label space: tenants past
    # this many distinct ids hash into 8 shared overflow buckets
    # ("~<n>"); tenantless requests share "_anon".
    "VDT_QOS_MAX_TRACKED_TENANTS":
    lambda: max(1, int(os.getenv("VDT_QOS_MAX_TRACKED_TENANTS", "64"))),
    # --- Quantized communication plane (parallel/collectives.py +
    # distributed/kv_transfer/quant.py) ----------------------------------
    # Master switch: "1" ships cross-device bytes block-scaled int8
    # (EQuARX-style in-graph collectives for the TKNP decode psum, the
    # MoE-EP all-to-alls and the dense-TP row-parallel reduce, plus the
    # quantized KV-transfer payload codec for dcn_pull / p2p /
    # shared_storage). "0" (default) keeps every path byte-identical to
    # the unquantized plane. In-graph gating is read at TRACE time —
    # flip it before building an engine, not mid-serving.
    "VDT_QCOMM":
    lambda: os.getenv("VDT_QCOMM", "0") == "1",
    # Per-path override: comma list of paths to quantize when VDT_QCOMM
    # is on ("" = all paths). Tokens: "tknp" (token-axis attention
    # psum), "ep" (MoE expert-parallel all-to-all + combine psum + the
    # re-replicate all-gather), "tp" (dense-model row-parallel output
    # reduce), "tpla" (TPLA latent-attention output combine), "tknp_kv"
    # (the TKNP KV-write shuffle: the step's new K/V rows crossing the
    # token-axis shard_map boundary to the page-owning rank), "kv"
    # (every KV-transfer connector payload) or an individual connector
    # name ("dcn_pull"/"p2p"/"shared_storage").
    "VDT_QCOMM_PATHS":
    lambda: os.getenv("VDT_QCOMM_PATHS", ""),
    # Quantization block (elements per fp32 scale). Payload codecs clip
    # it to the per-page-per-head span so no scale ever crosses a page
    # or head boundary; in-graph collectives use it as-is.
    "VDT_QCOMM_BLOCK":
    lambda: max(16, int(os.getenv("VDT_QCOMM_BLOCK", "256"))),
    # --- Telemetry plane ------------------------------------------------
    # SLO targets scored by the output processor over the request
    # timeline: time-to-first-token and time-per-output-token budgets in
    # milliseconds. 0 disables that target; both 0 disables goodput
    # accounting entirely (vdt:slo_* families are then not rendered).
    "VDT_SLO_TTFT_MS":
    lambda: float(os.getenv("VDT_SLO_TTFT_MS", "0")),
    "VDT_SLO_TPOT_MS":
    lambda: float(os.getenv("VDT_SLO_TPOT_MS", "0")),
    # Device/compilation telemetry (per-worker recompile counter,
    # device-wait timer, jax device-memory high-water mark). Read once
    # per worker at construction.
    "VDT_DEVICE_TELEMETRY":
    lambda: os.getenv("VDT_DEVICE_TELEMETRY", "1") == "1",
    # Transport telemetry (KV-transfer bytes/latency/inflight, shm-ring
    # wait/lag). Checked per record so the bench harness can flip it
    # between legs of one process.
    "VDT_TRANSPORT_TELEMETRY":
    lambda: os.getenv("VDT_TRANSPORT_TELEMETRY", "1") == "1",
    # --- Distributed trace plane (trace_plane.py) -----------------------
    # Master switch: "1" mints a trace_id + parent-span context at
    # admission, carries it on EngineCoreRequest over the msgpack wire
    # (old-wire tolerant), stamps it onto every EventRecorder event, and
    # hosts the front-end TraceAssembler + bounded flight recorder +
    # GET /debug/trace Perfetto export. "0" (default) mints nothing and
    # stamps nothing — the wire bytes and event details are
    # byte-identical to the pre-trace-plane behavior. Read ONCE per
    # component at construction.
    "VDT_TRACE_PLANE":
    lambda: os.getenv("VDT_TRACE_PLANE", "0") == "1",
    # Flight-recorder bound: max distinct traces the assembler retains
    # (oldest-admitted evicted past the bound) and max spans kept per
    # trace (earliest kept — a trace's causal root matters most).
    "VDT_TRACE_MAX_TRACES":
    lambda: max(8, int(os.getenv("VDT_TRACE_MAX_TRACES", "256"))),
    "VDT_TRACE_MAX_SPANS":
    lambda: max(16, int(os.getenv("VDT_TRACE_MAX_SPANS", "512"))),
    # --- SLO burn-rate watchdog (metrics/stats.py) ----------------------
    # Burn-rate threshold: a window burns when its miss rate exceeds
    # threshold * (1 - VDT_SLO_TARGET) (the error budget). The watchdog
    # runs whenever SLO targets are configured; DEGRADED (both the fast
    # and slow window burning) surfaces in /health + /debug/engine and
    # is offered to VDT_FLEET_SIGNALS as scale-out pressure. <= 0
    # disables the degraded flag while keeping the gauges.
    "VDT_SLO_BURN_THRESHOLD":
    lambda: float(os.getenv("VDT_SLO_BURN_THRESHOLD", "2.0")),
    # SLO availability target the error budget derives from (e.g. 0.99
    # = 1% of scored requests may miss their latency targets).
    "VDT_SLO_TARGET":
    lambda: min(0.9999, max(0.5, float(
        os.getenv("VDT_SLO_TARGET", "0.99")))),
    # --- Correctness sentinel (correctness_plane.py) --------------------
    # Master switch: "1" constructs the CorrectnessPlane on the DP
    # front-end (canary probe injector + reference journal +
    # cross-replica vote + numerics drift watch) and the model runner's
    # pre-sampling numerics tap. "0" (default) constructs NOTHING — no
    # injector, no extra jitted program, no new stats keys, old wire
    # bytes. Read ONCE per component at construction.
    "VDT_CORRECTNESS":
    lambda: os.getenv("VDT_CORRECTNESS", "0") == "1",
    # Seconds between canary probe rounds (each round fans one pinned
    # greedy golden prompt out to every in-rotation DP replica). <= 0
    # probes on every maintenance tick (tests/bench drills).
    "VDT_CANARY_INTERVAL_S":
    lambda: float(os.getenv("VDT_CANARY_INTERVAL_S", "30")),
    # Consecutive divergent canary rounds before a replica's suspicion
    # hardens into a fleet quarantine hint (and the vdt:replica_suspect
    # gauge latches). 2 keeps detection within <= 3 probes of a seeded
    # corruption while one transient mismatch never quarantines.
    "VDT_CANARY_QUARANTINE_N":
    lambda: max(1, int(os.getenv("VDT_CANARY_QUARANTINE_N", "2"))),
    # Numerics drift threshold: a replica whose rolling logits-entropy
    # mean deviates from the fleet mean by more than this fraction of
    # the fleet mean is drift-suspect. <= 0 disables the drift detector
    # while keeping the NaN watch and histograms.
    "VDT_NUMERICS_DRIFT_FRAC":
    lambda: float(os.getenv("VDT_NUMERICS_DRIFT_FRAC", "0.5")),
    # Deterministic fault injection: "name:rate[@delay_s],..." over the
    # named fault points of utils/fault_injection.py (kv_pull.drop,
    # kv_pull.delay, registry.truncate, engine_core.die,
    # heartbeat.stall, core_proc.spawn_fail, restart.storm,
    # admission.stall). Read at process start (spawned engine cores
    # inherit it); "" disables. Robustness drills/tests only.
    "VDT_FAULT_INJECT":
    lambda: os.getenv("VDT_FAULT_INJECT", ""),
}


def __getattr__(name: str) -> Any:
    if name in environment_variables:
        return environment_variables[name]()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__() -> list[str]:
    return list(environment_variables.keys())
