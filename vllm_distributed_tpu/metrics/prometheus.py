"""Prometheus text rendering of engine stats.

Reference: vllm/v1/metrics/prometheus.py + loggers.py:143
(PrometheusStatLogger gauges/counters served at /metrics). The stats dict
comes from Scheduler.get_stats() — rendered directly into the exposition
format so scraping works without the prometheus_client registry (which
is process-global and complicates multi-engine tests); names mirror the
reference's vllm:* metric family.
"""

_GAUGES = {
    "num_running_reqs": ("vdt:num_requests_running",
                         "Number of requests currently running"),
    "num_waiting_reqs": ("vdt:num_requests_waiting",
                         "Number of requests waiting to be scheduled"),
    "kv_cache_usage": ("vdt:kv_cache_usage_perc",
                       "Fraction of KV pages in use"),
    "spec_acceptance_rate": ("vdt:spec_decode_acceptance_rate",
                             "Accepted / proposed draft tokens"),
    # Engine-core batch pipeline (PP microbatches / async scheduling).
    "inflight_batches": ("vdt:inflight_batches",
                         "Dispatched-but-unretired batches in the "
                         "engine core's pipeline right now"),
    "max_concurrent_batches": ("vdt:max_concurrent_batches",
                               "Peak in-flight batch depth since start "
                               "(>= 2 proves host/device overlap "
                               "happened)"),
    "decode_overlap_frac": ("vdt:decode_overlap_frac",
                            "Fraction of dispatches issued while "
                            "another batch was already executing"),
    # Step batch composition (most recent non-empty step). Under DP
    # these sum across replicas — the fleet's current step mix, same
    # as PromQL sum() over per-instance gauges.
    "last_step_prefill_tokens": ("vdt:step_prefill_tokens",
                                 "Prefill tokens granted in the most "
                                 "recent non-empty scheduler step "
                                 "(summed across DP replicas)"),
    "last_step_decode_tokens": ("vdt:step_decode_tokens",
                                "Decode tokens granted in the most "
                                "recent non-empty scheduler step "
                                "(summed across DP replicas)"),
    # SSM state cache (core/state_cache.py; stateful models only —
    # the scheduler omits the key otherwise).
    "ssm_state_bytes_held": ("vdt:ssm_state_bytes_held",
                             "Device bytes held by live SSM state "
                             "snapshots (summed across DP replicas)"),
}

_COUNTERS = {
    "num_preemptions": ("vdt:num_preemptions_total",
                        "Cumulative preempted requests"),
    "kv_window_pages_freed": ("vdt:kv_window_pages_freed_total",
                              "Cumulative pages the window cache group "
                              "handed back behind requests' windows "
                              "(absent for a model with one cache group)"),
    "hits": ("vdt:prefix_cache_hits_total",
             "Cumulative prefix-cache token hits"),
    "queries": ("vdt:prefix_cache_queries_total",
                "Cumulative prefix-cache token queries"),
    # Spec decode (reference: v1/metrics SpecDecodingStats -> the
    # vllm:spec_decode_* family).
    "spec_num_draft_tokens": ("vdt:spec_decode_num_draft_tokens_total",
                              "Cumulative proposed draft tokens"),
    "spec_num_accepted_tokens": (
        "vdt:spec_decode_num_accepted_tokens_total",
        "Cumulative accepted draft tokens"),
    "spec_num_drafts": ("vdt:spec_decode_num_drafts_total",
                        "Cumulative draft proposals"),
    # Fault-tolerance layer (scheduler watchdog + KV-pull retry).
    "watchdog_timeouts": ("vdt:watchdog_timeouts_total",
                          "Requests swept out of WAITING_FOR_REMOTE_KVS "
                          "by the watchdog deadline"),
    "kv_pull_retries": ("vdt:kv_pull_retries_total",
                        "Request-level remote-KV pull retries"),
    "kv_pull_failures": ("vdt:kv_pull_failures_total",
                         "Failed remote-KV pulls (each requeued for "
                         "retry or local recompute)"),
    # Engine-core batch pipeline throughput accounting.
    "steps_dispatched": ("vdt:engine_steps_dispatched_total",
                         "Batches dispatched by the engine core"),
    "steps_overlapped": ("vdt:engine_steps_overlapped_total",
                         "Batches dispatched while another was already "
                         "in flight"),
    "num_async_spec_grants": ("vdt:async_spec_grants_total",
                              "Speculative run-ahead decode grants "
                              "issued by the async scheduler"),
    # DP front-end recovery (dp_client failover + resurrection).
    "replica_failovers": ("vdt:replica_failovers_total",
                          "Dead DP replicas taken out of rotation with "
                          "their journaled requests migrated"),
    "replica_resurrections": ("vdt:replica_resurrections_total",
                              "Downed DP replicas successfully "
                              "restarted and returned to rotation"),
    # Request-lifecycle timeline (metrics/events.py).
    "timeline_events_dropped": ("vdt:timeline_events_dropped_total",
                                "Lifecycle events dropped by full ring "
                                "buffers (oldest-first overflow)"),
    # Compile-lattice size: graphs warmed by precompile() (summed across
    # DP replicas; the mega-kernel's collapsed lattice shows up here as
    # a smaller warm-up at unchanged bucket configs).
    "precompile_graphs": ("vdt:precompile_graphs_total",
                          "XLA graphs compiled by the precompile "
                          "warm-up suite"),
    # SSM state cache (core/state_cache.py): prefix-style admission at
    # snapshot boundaries for stateful (Mamba/Jamba) models.
    "ssm_state_cache_hits": ("vdt:ssm_state_cache_hits_total",
                             "Stateful admissions resumed from a state "
                             "snapshot instead of token 0"),
    "ssm_state_cache_queries": ("vdt:ssm_state_cache_queries_total",
                                "Stateful admission lookups against "
                                "the state-snapshot index"),
    "ssm_state_cache_evictions": ("vdt:ssm_state_cache_evictions_total",
                                  "State snapshots evicted (LRU) to "
                                  "make room for new checkpoints"),
    "ssm_checkpoints": ("vdt:ssm_checkpoints_total",
                        "SSM state snapshots committed at checkpoint "
                        "boundaries (periodic cadence + preempt parks)"),
    "ssm_journal_reclaimed": ("vdt:ssm_journal_reclaimed_total",
                              "Checkpoint-journal files reclaimed by "
                              "the retention sweep (TTL expiry + "
                              "size-budget eviction at init/sleep)"),
    "ssm_journal_demotions": ("vdt:ssm_journal_demotions_total",
                              "Evicted state snapshots demoted to the "
                              "checkpoint journal instead of discarded "
                              "(hierarchical tiering's journal-as-"
                              "second-tier; VDT_KV_TIERING=1)"),
}


# Histogram-valued stats entries: the engine ships them as
# {"buckets": [...], "counts": [...], "sum": s, "count": n} dicts
# (counts has one extra +Inf slot), rendered here in full exposition
# shape.
HISTOGRAMS = {
    "step_host_gap_seconds": (
        "vdt:step_host_gap_seconds",
        "Host gap between the return of a step's fetch and the launch "
        "of the next step's forward (batch queue: wait_model return to "
        "next dispatch), by the HOST's bookkeeping: 0 wherever the next "
        "forward was issued before the fetch began, which is every "
        "pipelined step, whether or not that forward had already ended "
        "(vdt:step_device_idle_at_issue asks the device)"),
    "step_period_seconds": (
        "vdt:step_period_seconds",
        "Step start to next step start while the engine has work (the "
        "synchronous and the pipelined step); the step phases sum to "
        "it"),
    "step_host_stall_seconds": (
        "vdt:step_host_stall_seconds",
        "Per step, wall time outside the device wait less the "
        "engine-core thread's own CPU time: time it wanted to run and "
        "did not (GIL held elsewhere, core taken, launch blocked); a "
        "mean: one step's reading errs by a tick of the CPU clock"),
    "step_cpu_seconds": (
        "vdt:step_cpu_seconds",
        "The engine-core thread's CPU seconds (time.thread_time()) "
        "between two step starts, one observation a period; "
        "vdt:step_phase_cpu_seconds prepare_inputs, launch and other "
        "sum to it"),
    "step_device_seen": (
        "vdt:step_device_seen",
        "Per forward, 1 where vdt:step_device_seconds observed it (the "
        "host saw both ends of it on the device), else 0: the mean is "
        "the share of the window's forwards that family covers"),
    "step_device_idle_at_issue": (
        "vdt:step_device_idle_at_issue",
        "Per forward, 1 where at its issue nothing was in flight or the "
        "forward in flight had already ended (is_ready() of the array "
        "its fetch waits for), else 0: the mean is the share of "
        "forwards issued to a chip that had run dry"),
    "input_queue_seconds": (
        "vdt:input_queue_seconds",
        "A request on the transport: the front end's put to the "
        "engine-core thread taking it (once a step while it has work); "
        "one time.monotonic() for the processes of a host"),
    "request_queue_seconds": (
        "vdt:request_queue_seconds",
        "add_request to the first step that grants the request tokens"),
    "step_kv_tokens_read": (
        "vdt:step_kv_tokens_read",
        "Per step, context lengths (computed + scheduled tokens) summed "
        "over the scheduled requests: K/V tokens the attention reads"),
    "step_decode_kv_tokens_walked": (
        "vdt:step_decode_kv_tokens_walked",
        "Per step, the K/V token slots that the paged attention kernel's "
        "decode groups move in one full layer: every single-token "
        "sequence's whole kv blocks (128 positions, or the page table's "
        "width) up to its own end. Over the single-token part of "
        "vdt:step_kv_tokens_read it is what the groups move for what "
        "their queries see; absent for a model without K/V pages"),
    "step_latent_prefill_tokens": (
        "vdt:step_latent_prefill_tokens",
        "Per step, query rows that latent attention served in the "
        "multi-head form (sequences with more than one query row in a "
        "chunk program on the latent kernels; 0 on a decode step and "
        "for a model without a latent cache)"),
    "step_kv_window_tokens_read": (
        "vdt:step_kv_window_tokens_read",
        "Per step, under a window cache group: min(context, window - 1 "
        "+ scheduled tokens) summed over the scheduled requests, the K/V "
        "tokens ONE window layer reads (vdt:step_kv_tokens_read is what "
        "a full layer reads); absent for a model with one cache group"),
    "step_requests_past_window": (
        "vdt:step_requests_past_window",
        "Per step, under a window cache group: the scheduled requests "
        "whose context (computed + scheduled tokens) is longer than the "
        "window, the ones whose window pages are being freed behind them; "
        "absent for a model with one cache group"),
    "step_ssd_chunk_tokens": (
        "vdt:step_ssd_chunk_tokens",
        "Per step, tokens that the Mamba-2 layers took in the chunked "
        "form (ops/ssd.py ssd_chunk: the segments longer than one token "
        "of a step whose chunks fit the program's static count; 0 on a "
        "decode step, on the token-by-token fallback and for a model "
        "without such layers)"),
    "step_retention_chunk_tokens": (
        "vdt:step_retention_chunk_tokens",
        "Per step, tokens that the power-retention layers took in the "
        "chunked form (ops/power_retention.py power_retention_chunk: the "
        "segments longer than one token of a step whose chunks fit the "
        "program's static count; 0 on a decode step, on the "
        "token-by-token fallback and for a model without such layers)"),
    "step_host_transfers": (
        "vdt:step_host_transfers",
        "Per step, host arrays the model runner's _prepare_inputs and "
        "launch handed to the device explicitly (3 on the default path: "
        "packed forward inputs, packed sampling inputs, sampled rows)"),
}

# Stats entries that map label values to a histogram dict:
# key -> (family, HELP, label names). A family of two labels keys its
# map by the values joined with "/" ("decode/period").
LABELED_HISTOGRAMS = {
    "step_phase_seconds": (
        "vdt:step_phase_seconds",
        "Wall seconds per engine-core step phase", ("phase", )),
    "step_phase_cpu_seconds": (
        "vdt:step_phase_cpu_seconds",
        "The engine-core thread's CPU seconds in prepare_inputs and in "
        "launch (wall less CPU seconds of the same phase is what the "
        "thread waited there: GIL, a core, the runtime) and in other, "
        "the rest of a period by subtraction: the three sum to "
        "vdt:step_cpu_seconds (dispatch, wait: pipeline parallelism's "
        "own blocks)", ("phase", )),
    "step_kind_seconds": (
        "vdt:step_kind_seconds",
        "Step period and device wait (part) told apart by the kind of "
        "step: mixed = the batch whose results the step fetched held a "
        "prefill token, decode = none", ("kind", "part")),
    "step_device_seconds": (
        "vdt:step_device_seconds",
        "The chip's time for one forward and its sampler on the host's "
        "clock, where the host saw both ends: from its issue if that "
        "found the chip idle, else from the end of the forward before "
        "it, to the return of a fetch that truly waited; by shape: "
        "decode = no prefill token, chunk = a prefill token in the "
        "largest token bucket, mixed = any other", ("shape", )),
    "step_fetch_seconds": (
        "vdt:step_fetch_seconds",
        "Fetch of a forward's results, by whether they were there when "
        "it began (is_ready()): ready 1 = what the copies cost with "
        "nothing to wait for, ready 0 = a true wait for the device",
        ("ready", )),
    "step_tokens": (
        "vdt:step_tokens",
        "Tokens granted per non-empty step, by kind (prefill = requests "
        "still in their prompt, decode = past it)", ("kind", )),
    "step_attn_pairs": (
        "vdt:step_attn_pairs",
        "Per step, under a window cache group: query x key pairs inside "
        "the masks of ONE layer of each kind (full: every key up to the "
        "query; window: the last `sliding_window` of them)",
        ("layer_kind", )),
}


# Label names per labeled vdt: family — the single source of truth for
# the renderers below AND scripts/lint_metrics.py (which parses this
# literal and cross-checks every entry against the README metrics
# table, so an undocumented label set fails tier-1).
LABELED_METRICS = {
    # The front end's per-token path (metrics/stats.py FrontendStats).
    "vdt:frontend_detokenized_tokens_total": ("path", ),
    "vdt:frontend_request_outputs_total": ("kind", ),
    "vdt:step_phase_seconds": ("phase", ),
    "vdt:step_phase_cpu_seconds": ("phase", ),
    "vdt:step_kind_seconds": ("kind", "part"),
    "vdt:step_device_seconds": ("shape", ),
    "vdt:step_fetch_seconds": ("ready", ),
    "vdt:step_tokens": ("kind", ),
    "vdt:step_attn_pairs": ("layer_kind", ),
    # Two kinds of K/V in one manager (core/kv_cache_manager.py).
    "vdt:kv_group_pages_held": ("group", ),
    "vdt:fault_injections_total": ("point", ),
    # Telemetry plane: per-worker device/compilation series.
    "vdt:recompiles_total": ("worker", ),
    "vdt:device_memory_peak_bytes": ("worker", ),
    "vdt:device_memory_in_use_bytes": ("worker", ),
    "vdt:device_wait_seconds": ("worker", ),
    # TPLA latent-pool geometry (ops/mla.py; MLA models only).
    "vdt:tpla_latent_shards": ("worker", ),
    "vdt:mla_latent_page_bytes": ("worker", ),
    "vdt:mla_latent_token_bytes": ("worker", ),
    # One chip's share of an expert layer (models/deepseek.py).
    "vdt:moe_experts_held": ("worker", ),
    "vdt:moe_experts_published": ("worker", ),
    # One sequence's fixed-size recurrent state (models/bailing.py).
    "vdt:state_row_bytes": ("worker", ),
    # Telemetry plane: per-connector KV transfer + shm ring.
    "vdt:kv_transfer_bytes_total": ("connector", "direction"),
    "vdt:kv_transfer_failures_total": ("connector", ),
    "vdt:kv_transfer_inflight": ("connector", ),
    "vdt:kv_transfer_seconds": ("connector", ),
    "vdt:shm_ring_messages_total": ("side", ),
    "vdt:shm_ring_wait_seconds": ("side", ),
    # Telemetry plane: block-pool introspection.
    "vdt:kv_blocks": ("state", ),
    "vdt:preemptions_by_cause_total": ("cause", ),
    # Hierarchical KV memory (core/kv_tier.py; VDT_KV_TIERING=1):
    # spill-tier occupancy and flow, by tier (host|disk).
    "vdt:kv_tier_pages": ("tier", ),
    "vdt:kv_tier_bytes": ("tier", ),
    "vdt:kv_tier_demotions_total": ("tier", ),
    "vdt:kv_tier_demotion_bytes_total": ("tier", ),
    "vdt:kv_tier_promotions_total": ("tier", ),
    "vdt:kv_tier_misses_total": ("tier", ),
    # Attention dispatch: which kernel family each step ran
    # (unified|decode|general|cascade|naive: five values).
    "vdt:attn_kernel_calls_total": ("kernel", ),
    # Quantized communication plane (parallel/collectives.py +
    # kv_transfer/quant.py): per-path wire/disk bytes saved.
    "vdt:qcomm_bytes_saved_total": ("path", ),
    # DP balancer + routing tier (engine/dp_client.py, engine/router.py).
    "vdt:dp_replica_load": ("replica", ),
    "vdt:router_prefix_index_entries": ("replica", ),
    # Disaggregated serving tier (engine/disagg.py).
    "vdt:disagg_fallbacks_total": ("reason", ),
    "vdt:pool_occupancy": ("pool", ),
    # Weighted admission shedding (entrypoints/openai/admission.py).
    "vdt:requests_shed_by_class_total": ("class", ),
    # Elastic-fleet control loop (engine/fleet.py; VDT_FLEET=1):
    # ticks/actions skipped, by freeze reason (stale_stats | budget |
    # scale_stall | at_max | asym_tp | partition).
    "vdt:fleet_freezes_total": ("reason", ),
    # HA control plane (engine/control_plane.py; VDT_FLEET_CONTROLLER
    # =1): stale-epoch/standby actuations rejected by the coordinator
    # fence, by action (scale_out | scale_in | retire | convert |
    # resplit | force_cycle | resurrect) — a fixed enum.
    "vdt:fleet_fenced_actions_total": ("action", ),
    # Per-tenant QoS (core/sched/qos.py; VDT_QOS=1). Label cardinality
    # is bounded: tenants past VDT_QOS_MAX_TRACKED_TENANTS hash into 8
    # shared "~<n>" overflow buckets, tenantless traffic shares
    # "_anon" (qos.bucket_tenant is the shared bucketing function;
    # each component's first-come tracked set is its own, so overflow
    # assignment can differ per replica past the cap).
    "vdt:tenant_granted_tokens_total": ("tenant", ),
    "vdt:tenant_kv_blocks": ("tenant", ),
    "vdt:tenant_preemptions_total": ("tenant", ),
    "vdt:tenant_goodput_frac": ("tenant", ),
    # SLO burn-rate watchdog (metrics/stats.py BurnRateWatchdog): error
    # budget burn per rolling window (a fixed enum: 1m | 10m).
    "vdt:slo_burn_rate": ("window", ),
    # Correctness sentinel (correctness_plane.py; VDT_CORRECTNESS=1).
    # All per-replica — a cross-replica sum would erase exactly the
    # per-replica divergence the sentinel exists to expose. Causes are
    # a fixed enum: reference | logprob | vote | timeout | nan_logits
    # | numerics_drift.
    "vdt:canary_probes_total": ("replica", ),
    "vdt:canary_divergences_total": ("replica", "cause"),
    "vdt:replica_suspect": ("replica", ),
    "vdt:logits_nan_steps_total": ("replica", ),
    "vdt:logits_entropy": ("replica", ),
    "vdt:logits_top_margin": ("replica", ),
}


def _render_dp_balancer(stats: dict) -> list[str]:
    """DP front-end balancer gauges: per-replica live request counts
    and the alive-replica count. Rendered whenever the stats flowed
    through DPEngineClient — with the router ON or OFF, so replica
    imbalance stays visible while debugging either path."""
    counts = stats.get("dp_request_counts")
    if not isinstance(counts, list) or not counts:
        return []
    lines = ["# HELP vdt:dp_replica_load Live requests owned by each "
             "DP replica (the balancer's routing load signal)",
             "# TYPE vdt:dp_replica_load gauge"]
    lines += [f'vdt:dp_replica_load{{replica="{i}"}} {int(n)}'
              for i, n in enumerate(counts)]
    down = stats.get("dp_replicas_down") or []
    lines += ["# HELP vdt:replicas_in_rotation DP replicas currently "
              "alive and accepting placements",
              "# TYPE vdt:replicas_in_rotation gauge",
              f"vdt:replicas_in_rotation {len(counts) - len(down)}"]
    return lines


def _render_router(router: dict) -> list[str]:
    """Routing-tier families from the front-end ReplicaRouter (one
    instance owns fleet placement, so values are exact, not merged)."""
    lines: list[str] = []
    for name, key, help_text in (
        ("vdt:router_requests_routed_total", "requests_routed",
         "Admissions placed by the routing tier"),
        ("vdt:router_affinity_hits_total", "affinity_hits",
         "Admissions routed to a replica already holding part of "
         "their prefix"),
        ("vdt:router_spillovers_total", "spillovers",
         "Admissions whose affinity home was overridden because it "
         "was pressured"),
        ("vdt:router_stale_degradations_total", "stale_degradations",
         "Admissions placed by pure load balancing because every "
         "load snapshot was stale"),
    ):
        lines += [f"# HELP {name} {help_text}", f"# TYPE {name} counter",
                  f"{name} {int(router.get(key, 0))}"]
    entries = router.get("prefix_index_entries")
    if isinstance(entries, list) and entries:
        name = "vdt:router_prefix_index_entries"
        lines += [f"# HELP {name} Prefix-residency index entries per "
                  "replica (bounded LRU of page hashes)",
                  f"# TYPE {name} gauge"]
        lines += [f'{name}{{replica="{i}"}} {int(n)}'
                  for i, n in enumerate(entries)]
    return lines


def _render_disagg(disagg: dict) -> list[str]:
    """Disagg serving-tier families from the DisaggCoordinator (one
    coordinator owns every handoff, so values are exact)."""
    from vllm_distributed_tpu.metrics.stats import render_histogram_lines
    name = "vdt:disagg_handoffs_total"
    lines = [f"# HELP {name} Prefill->decode handoffs admitted by the "
             "disagg coordinator",
             f"# TYPE {name} counter",
             f"{name} {int(disagg.get('handoffs', 0))}"]
    name = "vdt:disagg_fallbacks_total"
    fallbacks = disagg.get("fallbacks") or {}
    lines += [f"# HELP {name} Disagg recovery-ladder fallbacks by "
              "reason (local_reprefill = failed/stalled pull recomputed "
              "on the decode home, pull_retry = bounded re-pull, "
              "prefill_death / decode_death = replica died mid-stage "
              "and the request re-admitted, pool_down = whole pool out "
              "of rotation, no_pull_coords = prompt under one page)",
              f"# TYPE {name} counter"]
    lines += [f'{name}{{reason="{r}"}} {int(n)}'
              for r, n in sorted(fallbacks.items())]
    h = disagg.get("handoff_seconds")
    if isinstance(h, dict):
        name = "vdt:disagg_handoff_seconds"
        lines += render_histogram_lines(
            name, "Wall seconds from handoff interception to the decode "
            "home's first token (routing + KV pull or its fallback + "
            "requeue + first decode step)",
            h.get("buckets", ()), h.get("counts", ()),
            h.get("sum", 0.0), h.get("count", 0))
    occ = disagg.get("pool_occupancy") or {}
    if occ:
        name = "vdt:pool_occupancy"
        lines += [f"# HELP {name} Live requests owned by each disagg "
                  "pool (prefill/decode)",
                  f"# TYPE {name} gauge"]
        lines += [f'{name}{{pool="{p}"}} {int(n)}'
                  for p, n in sorted(occ.items())]
    return lines


def _render_fleet(fleet: dict) -> list[str]:
    """Elastic-fleet control-loop families (engine/fleet.py; present
    only while VDT_FLEET=1 on a DP deployment)."""
    lines: list[str] = []
    for key, name, kind, help_text in (
        ("replicas", "vdt:fleet_replicas", "gauge",
         "DP replicas currently in rotation (not down, not retired)"),
        ("draining", "vdt:fleet_draining", "gauge",
         "Replicas draining toward retirement or a pool conversion"),
        ("scale_outs", "vdt:fleet_scale_outs_total", "counter",
         "Replicas added to rotation by the fleet controller"),
        ("scale_ins", "vdt:fleet_scale_ins_total", "counter",
         "Replicas drained and retired under the low watermark"),
        ("resplits", "vdt:fleet_resplits_total", "counter",
         "Live prefill<->decode pool conversions completed"),
        ("wedge_cycles", "vdt:fleet_wedge_cycles_total", "counter",
         "Alive-but-not-stepping replicas force-cycled (work migrated "
         "via the continuation journal, then restart-probed)"),
        ("warm_start_pages", "vdt:fleet_warm_start_pages_total",
         "counter",
         "Spill-tier pages found by new/converted replicas warm-"
         "starting from the shared tier-2 namespace"),
        ("quarantines", "vdt:fleet_quarantines_total", "counter",
         "Suspect replicas force-cycled on the correctness sentinel's "
         "quarantine hints (VDT_CORRECTNESS + VDT_FLEET_SIGNALS; same "
         "drain+respawn rung as a wedge cycle)"),
    ):
        if key in fleet:
            lines += [f"# HELP {name} {help_text}",
                      f"# TYPE {name} {kind}",
                      f"{name} {int(fleet.get(key, 0))}"]
    freezes = fleet.get("freezes") or {}
    name = "vdt:fleet_freezes_total"
    lines += [f"# HELP {name} Fleet actuation skipped, by reason "
              "(stale_stats = a rotation member's stats went quiet, "
              "budget = action budget exhausted, scale_stall = replica "
              "spawn failed, at_max = device budget reached, asym_tp = "
              "pools differ in per-replica world size, partition = "
              "control plane unreachable)",
              f"# TYPE {name} counter"]
    lines += [f'{name}{{reason="{r}"}} {int(n)}'
              for r, n in sorted(freezes.items())]
    # HA control plane (engine/control_plane.py; keys present only
    # with VDT_FLEET_CONTROLLER=1).
    for key, name, kind, help_text in (
        ("leader", "vdt:fleet_leader", "gauge",
         "1 while THIS front-end's controller holds the fleet lease "
         "(0 on standbys and partitioned/dead controllers)"),
        ("lease_epoch", "vdt:fleet_lease_epoch", "gauge",
         "Fencing epoch of the lease this controller last held "
         "(bumped by the coordinator on every holder change)"),
        ("leader_transitions", "vdt:fleet_leader_transitions_total",
         "counter",
         "Lease holder changes since boot (election + every "
         "failover takeover)"),
    ):
        if key in fleet:
            lines += [f"# HELP {name} {help_text}",
                      f"# TYPE {name} {kind}",
                      f"{name} {int(fleet.get(key, 0))}"]
    if "fenced_actions" in fleet:
        fenced = fleet.get("fenced_actions") or {}
        name = "vdt:fleet_fenced_actions_total"
        lines += [f"# HELP {name} Actuations rejected by the "
                  "coordinator's epoch fence (stale ex-leader "
                  "commands) or skipped on a standby, by action",
                  f"# TYPE {name} counter"]
        lines += [f'{name}{{action="{a}"}} {int(n)}'
                  for a, n in sorted(fenced.items())]
    return lines


def _render_worker_telemetry(workers: dict) -> list[str]:
    """Per-worker device/compilation series from the DP-merged
    ``{worker_label: stats}`` map (labels are fleet-unique, so every
    series survives the merge unsummed)."""
    from vllm_distributed_tpu.metrics.stats import render_histogram_lines
    lines: list[str] = []
    families = (
        ("num_recompiles", "vdt:recompiles_total", "counter",
         "Graphs compiled AFTER precompile warm-up (a steady-state "
         "recompile is a shape-lattice leak)"),
        ("device_memory_peak_bytes", "vdt:device_memory_peak_bytes",
         "gauge", "Peak device HBM bytes in use (weights + workspace "
         "+ KV high-water mark)"),
        ("device_memory_in_use_bytes", "vdt:device_memory_in_use_bytes",
         "gauge", "Device HBM bytes in use at the last stats poll"),
        # MLA latent-pool geometry (ops/mla.py TPLA layout; present only
        # for MLA models): shards > 1 = the latent cache is TP-sharded,
        # page bytes = PER-RANK HBM one latent page costs this worker.
        ("tpla_latent_shards", "vdt:tpla_latent_shards", "gauge",
         "TP shards of the MLA latent KV cache (1 = replicated layout "
         "/ VDT_TPLA off)"),
        ("mla_latent_page_bytes", "vdt:mla_latent_page_bytes", "gauge",
         "Per-rank HBM bytes one MLA latent page costs (1/TP of the "
         "replicated row under TPLA, plus the rope sidecar)"),
        ("mla_latent_token_bytes", "vdt:mla_latent_token_bytes", "gauge",
         "HBM bytes of one token's latent rows over every layer: what "
         "a token read by vdt:step_kv_tokens_read costs an MLA model"),
        ("moe_experts_held", "vdt:moe_experts_held", "gauge",
         "Routed experts whose weights this worker holds under an "
         "expert share (config.json expert_share)"),
        ("moe_experts_published", "vdt:moe_experts_published", "gauge",
         "Routed experts the router scores under an expert share: the "
         "published count of the deployment this worker is one chip of"),
        ("state_row_bytes", "vdt:state_row_bytes", "gauge",
         "HBM bytes of one sequence's recurrent and convolution state "
         "over every linear-attention layer: what a request costs "
         "whatever its length"),
    )
    for key, name, kind, help_text in families:
        series = [(w, s[key]) for w, s in sorted(workers.items())
                  if isinstance(s, dict) and key in s]
        if not series:
            continue
        lines += [f"# HELP {name} {help_text}", f"# TYPE {name} {kind}"]
        lines += [f'{name}{{worker="{w}"}} {float(v)}'
                  for w, v in series]
    hist_name = "vdt:device_wait_seconds"
    first = True
    for worker, s in sorted(workers.items()):
        h = s.get("device_wait_seconds") if isinstance(s, dict) else None
        if not isinstance(h, dict):
            continue
        if first:
            lines += [f"# HELP {hist_name} Wall seconds the worker "
                      "blocked fetching a step's device results",
                      f"# TYPE {hist_name} histogram"]
            first = False
        lines += render_histogram_lines(
            hist_name, "", h.get("buckets", ()), h.get("counts", ()),
            h.get("sum", 0.0), h.get("count", 0),
            label=f'worker="{worker}"', header=False)
    return lines


def _render_transport(transport: dict) -> list[str]:
    """Per-connector KV-transfer and shm-ring families from a (possibly
    DP-merged) TransportRecorder snapshot."""
    from vllm_distributed_tpu.metrics.stats import render_histogram_lines
    lines: list[str] = []
    kv = {c: e for c, e in (transport.get("kv") or {}).items()
          if isinstance(e, dict)}
    if kv:
        name = "vdt:kv_transfer_bytes_total"
        lines += [f"# HELP {name} Bytes moved per KV-transfer "
                  "connector and direction (tx = served/saved, rx = "
                  "pulled/loaded)",
                  f"# TYPE {name} counter"]
        for conn in sorted(kv):
            for direction in ("tx", "rx"):
                lines.append(
                    f'{name}{{connector="{conn}",'
                    f'direction="{direction}"}} '
                    f'{int(kv[conn].get(f"{direction}_bytes", 0))}')
        name = "vdt:kv_transfer_failures_total"
        lines += [f"# HELP {name} Failed transfers per connector",
                  f"# TYPE {name} counter"]
        lines += [f'{name}{{connector="{c}"}} '
                  f'{int(kv[c].get("failures", 0))}' for c in sorted(kv)]
        name = "vdt:kv_transfer_inflight"
        lines += [f"# HELP {name} Transfers in flight right now per "
                  "connector",
                  f"# TYPE {name} gauge"]
        lines += [f'{name}{{connector="{c}"}} '
                  f'{int(kv[c].get("inflight", 0))}' for c in sorted(kv)]
        name = "vdt:kv_transfer_seconds"
        lines += [f"# HELP {name} Wall seconds per transfer, by "
                  "connector",
                  f"# TYPE {name} histogram"]
        for conn in sorted(kv):
            h = kv[conn].get("seconds")
            if isinstance(h, dict):
                lines += render_histogram_lines(
                    name, "", h.get("buckets", ()), h.get("counts", ()),
                    h.get("sum", 0.0), h.get("count", 0),
                    label=f'connector="{conn}"', header=False)
    shm = {s: e for s, e in (transport.get("shm") or {}).items()
           if isinstance(e, dict)}
    if shm:
        name = "vdt:shm_ring_messages_total"
        lines += [f"# HELP {name} Messages through the shm broadcast "
                  "ring, by side",
                  f"# TYPE {name} counter"]
        lines += [f'{name}{{side="{s}"}} '
                  f'{int(shm[s].get("messages", 0))}'
                  for s in sorted(shm)]
        name = "vdt:shm_ring_wait_seconds"
        lines += [f"# HELP {name} Wall seconds blocked in the native "
                  "ring write/read per message",
                  f"# TYPE {name} histogram"]
        for side in sorted(shm):
            h = shm[side].get("wait_seconds")
            if isinstance(h, dict):
                lines += render_histogram_lines(
                    name, "", h.get("buckets", ()), h.get("counts", ()),
                    h.get("sum", 0.0), h.get("count", 0),
                    label=f'side="{side}"', header=False)
    if kv or shm:
        name = "vdt:shm_ring_lag_chunks"
        lines += [f"# HELP {name} Reader backlog in ring CHUNKS "
                  "(writer_seq - reader_seq; a multi-chunk message "
                  "counts once per chunk) at the last dequeue; max "
                  "across DP replicas",
                  f"# TYPE {name} gauge",
                  f'{name} {int(transport.get("shm_lag_chunks", 0))}']
    return lines


def _render_qcomm(transport_qcomm, remote=None) -> list[str]:
    """Quantized-communication plane counters. Three sources merge
    here: the (possibly DP-merged) per-core telemetry recorders carry
    the connector payload paths exactly, parallel/collectives.py's
    trace-time counters carry this process's in-graph tknp/ep/tp
    paths, and ``remote`` carries the pid-deduped follower-process
    in-graph snapshots dp_client merged off the get_stats feed (so
    spawned cores' savings are no longer invisible — the
    vdt:fault_injections_total fix rides the same feed)."""
    from vllm_distributed_tpu.parallel import collectives
    merged = collectives.merged_qcomm_view(
        transport_qcomm if isinstance(transport_qcomm, dict) else None,
        remote if isinstance(remote, dict) else None)
    if not merged:
        return []
    name = "vdt:qcomm_bytes_saved_total"
    lines = [f"# HELP {name} Wire/disk bytes the quantized "
             "communication plane saved vs raw precision, per path "
             "(connector paths exact; in-graph paths analytic "
             "per-traced-collective)",
             f"# TYPE {name} counter"]
    lines += [f'{name}{{path="{p}"}} {int(merged[p]["bytes_saved"])}'
              for p in sorted(merged)]
    name = "vdt:qcomm_fallbacks_total"
    lines += [f"# HELP {name} Quantized payloads/collectives that "
              "degraded to raw precision (corrupt scale header, "
              "inapplicable axis, sub-byte dtype)",
              f"# TYPE {name} counter",
              f"{name} {sum(int(e['fallbacks']) for e in merged.values())}"]
    return lines


def _render_kv_cache(kv: dict) -> list[str]:
    """Block-pool introspection families (free/used/tombstoned pages,
    fragmentation, windowed prefix-cache hit rate, preemption
    causes)."""
    lines: list[str] = []
    name = "vdt:kv_blocks"
    lines += [f"# HELP {name} KV pages by pool state (cached_free = "
              "reclaimable prefix-cache pages inside free)",
              f"# TYPE {name} gauge"]
    for state, key in (("free", "free_blocks"), ("used", "used_blocks"),
                       ("tombstoned", "tombstoned_blocks"),
                       ("cached_free", "cached_free_blocks")):
        lines.append(f'{name}{{state="{state}"}} '
                     f'{int(kv.get(key, 0))}')
    for name, key, help_text in (
        ("vdt:kv_fragmentation_frac", "fragmentation_frac",
         "Request-held page slots not covered by computed tokens "
         "(internal fragmentation)"),
        ("vdt:prefix_cache_hit_rate_window", "window_hit_rate",
         "Prefix-cache hit rate over the most recent lookups "
         "(sliding window)"),
    ):
        lines += [f"# HELP {name} {help_text}", f"# TYPE {name} gauge",
                  f"{name} {round(float(kv.get(key, 0.0)), 6)}"]
    causes = kv.get("preemption_causes")
    if isinstance(causes, dict) and causes:
        name = "vdt:preemptions_by_cause_total"
        lines += [f"# HELP {name} Preempted requests by cause "
                  "(capacity = evicted for another request's pages, "
                  "self = no eligible victim)",
                  f"# TYPE {name} counter"]
        lines += [f'{name}{{cause="{c}"}} {int(n)}'
                  for c, n in sorted(causes.items())]
    return lines


def _render_kv_tier(tier: dict) -> list[str]:
    """Hierarchical KV-memory families (core/kv_tier.py "kv_tier"
    stats entry, summed per tier across DP replicas)."""
    lines: list[str] = []
    for name, key, kind, help_text in (
        ("vdt:kv_tier_pages", "pages", "gauge",
         "Prefix pages currently held per spill tier (host = pinned "
         "host-RAM pool, disk = spill files)"),
        ("vdt:kv_tier_bytes", "bytes", "gauge",
         "Bytes currently held per spill tier"),
        ("vdt:kv_tier_demotions_total", "demotions", "counter",
         "Pages demoted into each tier (HBM eviction -> host, "
         "host-pool eviction -> disk)"),
        ("vdt:kv_tier_demotion_bytes_total", "demotion_bytes",
         "counter", "Bytes demoted into each tier"),
        ("vdt:kv_tier_promotions_total", "promotions", "counter",
         "Tier-resident pages promoted back into device pages at "
         "admission"),
        ("vdt:kv_tier_misses_total", "misses", "counter",
         "Tier lookups that failed despite an index entry (corrupt / "
         "missing / shape-foreign spill file -> clean recompute)"),
    ):
        per_tier = tier.get(key)
        if not isinstance(per_tier, dict):
            continue
        lines += [f"# HELP {name} {help_text}", f"# TYPE {name} {kind}"]
        lines += [f'{name}{{tier="{t}"}} {int(per_tier[t])}'
                  for t in sorted(per_tier)
                  if isinstance(per_tier[t], (int, float))]
    h = tier.get("promotion_seconds")
    if isinstance(h, dict):
        lines += _render_histogram(
            "vdt:kv_tier_promotion_seconds",
            "Host-side seconds to stage+dispatch one request's tier "
            "promotion (the scatter itself overlaps the forward)", h)
    return lines


def _render_tenants(tenants: dict) -> list[str]:
    """Per-tenant QoS families ({tenant: {granted_tokens, kv_blocks,
    preemptions}} from the scheduler's stats, summed per tenant across
    DP replicas). Cardinality is bounded at the source (qos.py
    bucket_tenant), so one series per bucket is safe to render."""
    lines: list[str] = []
    for name, key, kind, help_text in (
        ("vdt:tenant_granted_tokens_total", "granted_tokens", "counter",
         "Scheduler token grants per tenant bucket (the DRR charge "
         "stream)"),
        ("vdt:tenant_kv_blocks", "kv_blocks", "gauge",
         "KV pages currently held per tenant bucket"),
        ("vdt:tenant_preemptions_total", "preemptions", "counter",
         "Preemptions suffered per tenant bucket (all causes)"),
    ):
        lines += [f"# HELP {name} {help_text}", f"# TYPE {name} {kind}"]
        lines += [f'{name}{{tenant="{t}"}} {int(tenants[t].get(key, 0))}'
                  for t in sorted(tenants)
                  if isinstance(tenants[t], dict)]
    return lines


def _render_numerics(numerics: dict) -> list[str]:
    """In-flight numerics watch (correctness_plane.py NumericsTap;
    VDT_CORRECTNESS=1). DP ships {replica: snapshot} keyed by the
    aggregator; a single-engine deployment ships the runner's flat
    snapshot, rendered as replica 0. Per-replica series — NEVER summed:
    the drift detector's whole signal is replicas disagreeing."""
    from vllm_distributed_tpu.metrics.stats import render_histogram_lines
    if "nan_steps" in numerics:
        numerics = {0: numerics}
    per = {i: d for i, d in numerics.items() if isinstance(d, dict)}
    name = "vdt:logits_nan_steps_total"
    lines = [f"# HELP {name} Pre-sampling steps whose logits carried "
             "NaN/Inf, per replica (the poisoned step is excluded from "
             "the entropy/margin histograms)",
             f"# TYPE {name} counter"]
    lines += [f'{name}{{replica="{i}"}} {int(d.get("nan_steps", 0))}'
              for i, d in sorted(per.items())]
    for name, key, help_text in (
        ("vdt:logits_entropy", "entropy",
         "Per-step mean entropy of the pre-sampling logits, per "
         "replica (the numerics drift detector's primary signal)"),
        ("vdt:logits_top_margin", "top_margin",
         "Per-step mean top-1/top-2 logit margin, per replica (margin "
         "collapse flags quality degradation below the argmax)"),
    ):
        lines += [f"# HELP {name} {help_text}",
                  f"# TYPE {name} histogram"]
        for i, d in sorted(per.items()):
            h = d.get(key)
            if isinstance(h, dict):
                lines += render_histogram_lines(
                    name, "", h.get("buckets", ()), h.get("counts", ()),
                    h.get("sum", 0.0), h.get("count", 0),
                    label=f'replica="{i}"', header=False)
    return lines


def _render_correctness(cp: dict) -> list[str]:
    """Canary-probe families (correctness_plane.py; VDT_CORRECTNESS=1).
    One plane owns the fleet's canaries, so the counters attach exactly
    — the per-replica maps are labeled at the source, never merged."""
    lines: list[str] = []
    probes = cp.get("probes")
    if isinstance(probes, dict):
        name = "vdt:canary_probes_total"
        lines += [f"# HELP {name} Canary probes completed per replica "
                  "(pinned greedy golden prompts through the real "
                  "serving path)",
                  f"# TYPE {name} counter"]
        lines += [f'{name}{{replica="{i}"}} {int(n)}'
                  for i, n in sorted(probes.items())]
    div = cp.get("divergences")
    if isinstance(div, dict):
        name = "vdt:canary_divergences_total"
        lines += [f"# HELP {name} Correctness divergences per replica, "
                  "by cause (reference = tokens strayed from the "
                  "journal, logprob = fingerprint drift, vote = "
                  "cross-replica minority, timeout = probe unanswered, "
                  "nan_logits = NaN/Inf step, numerics_drift = entropy "
                  "window strayed from the fleet mean)",
                  f"# TYPE {name} counter"]
        lines += [f'{name}{{replica="{i}",cause="{c}"}} {int(n)}'
                  for i, causes in sorted(div.items())
                  if isinstance(causes, dict)
                  for c, n in sorted(causes.items())]
    suspects = cp.get("suspects")
    if isinstance(suspects, dict):
        name = "vdt:replica_suspect"
        lines += [f"# HELP {name} 1 while the correctness sentinel "
                  "holds live suspicion against the replica (any "
                  "strike ladder >= 1; clears on a clean round)",
                  f"# TYPE {name} gauge"]
        lines += [f'{name}{{replica="{i}"}} {int(v)}'
                  for i, v in sorted(suspects.items())]
    return lines


def _render_histogram(name: str, help_text: str, h: dict) -> list[str]:
    from vllm_distributed_tpu.metrics.stats import render_histogram_lines
    return render_histogram_lines(name, help_text, h.get("buckets", ()),
                                  h.get("counts", ()), h.get("sum", 0.0),
                                  h.get("count", 0))


def _render_labeled_histograms(name: str, help_text: str, labels: tuple,
                               hists: dict) -> list[str]:
    """One labeled histogram family (vdt:step_phase_seconds{phase},
    vdt:step_kind_seconds{kind,part}): HELP/TYPE once, then one series
    per label value(s) — bucket/+Inf shape comes from the shared
    exposition helper."""
    from vllm_distributed_tpu.metrics.stats import render_histogram_lines
    lines = [f"# HELP {name} {help_text}", f"# TYPE {name} histogram"]
    for value in sorted(hists):
        h = hists[value]
        if not isinstance(h, dict):
            continue
        lines += render_histogram_lines(
            name, "", h.get("buckets", ()), h.get("counts", ()),
            h.get("sum", 0.0), h.get("count", 0),
            label=",".join(f'{k}="{v}"' for k, v
                           in zip(labels, value.split("/"))),
            header=False)
    return lines


def render_metrics(stats: dict) -> str:
    lines: list[str] = []
    for key, (name, help_text) in _GAUGES.items():
        if key in stats:
            lines += [f"# HELP {name} {help_text}",
                      f"# TYPE {name} gauge",
                      f"{name} {float(stats[key])}"]
    for key, (name, help_text) in _COUNTERS.items():
        if key in stats:
            lines += [f"# HELP {name} {help_text}",
                      f"# TYPE {name} counter",
                      f"{name} {float(stats[key])}"]
    for key, (name, help_text) in HISTOGRAMS.items():
        value = stats.get(key)
        if isinstance(value, dict):
            lines += _render_histogram(name, help_text, value)
    for key, (name, help_text, labels) in LABELED_HISTOGRAMS.items():
        value = stats.get(key)
        if isinstance(value, dict) and value:
            lines += _render_labeled_histograms(name, help_text, labels,
                                                value)
    # Two kinds of K/V in one manager: what each group holds now.
    held = [(g, stats[f"kv_{g}_pages_held"]) for g in ("full", "window")
            if f"kv_{g}_pages_held" in stats]
    if held:
        name = "vdt:kv_group_pages_held"
        lines += [f"# HELP {name} Pages that requests hold in each cache "
                  "group of a model that mixes window and full attention "
                  "layers (full: every token of a request; window: what "
                  "lies inside its window, the rest is handed back)",
                  f"# TYPE {name} gauge"]
        lines += [f'{name}{{group="{g}"}} {float(v)}' for g, v in held]
    # Attention kernel dispatch counts ({kernel: steps} from the runner,
    # summed per kernel across DP replicas).
    calls = stats.get("attn_kernel_calls")
    if isinstance(calls, dict) and calls:
        name = "vdt:attn_kernel_calls_total"
        lines += [f"# HELP {name} Steps dispatched per attention kernel "
                  "family (unified = mixed-batch mega-kernel, decode = "
                  "SB-batched decode, general = per-sequence tiles, "
                  "cascade = shared-prefix, naive = XLA reference)",
                  f"# TYPE {name} counter"]
        lines += [f'{name}{{kernel="{k}"}} {int(calls[k])}'
                  for k in sorted(calls)]
    # Telemetry plane (worker device/compilation, transport, KV cache):
    # nested dicts shipped up the stats RPC, labeled at the source.
    workers = stats.get("workers")
    if isinstance(workers, dict) and workers:
        lines += _render_worker_telemetry(workers)
    transport = stats.get("transport")
    if isinstance(transport, dict):
        lines += _render_transport(transport)
    lines += _render_qcomm((transport or {}).get("qcomm")
                           if isinstance(transport, dict) else None,
                           stats.get("qcomm_traced_remote"))
    kv_cache = stats.get("kv_cache")
    if isinstance(kv_cache, dict) and kv_cache:
        lines += _render_kv_cache(kv_cache)
    kv_tier = stats.get("kv_tier")
    if isinstance(kv_tier, dict) and kv_tier:
        lines += _render_kv_tier(kv_tier)
    tenants = stats.get("tenants")
    if isinstance(tenants, dict) and tenants:
        lines += _render_tenants(tenants)
    # DP balancer load gauges + routing-tier counters (dp_client /
    # router stats entries; absent on single-replica deployments).
    lines += _render_dp_balancer(stats)
    router = stats.get("router")
    if isinstance(router, dict):
        lines += _render_router(router)
    disagg = stats.get("disagg")
    if isinstance(disagg, dict):
        lines += _render_disagg(disagg)
    fleet = stats.get("fleet")
    if isinstance(fleet, dict) and fleet:
        lines += _render_fleet(fleet)
    # Correctness sentinel (correctness_plane.py; keys present only
    # while VDT_CORRECTNESS=1).
    numerics = stats.get("numerics")
    if isinstance(numerics, dict) and numerics:
        lines += _render_numerics(numerics)
    correctness = stats.get("correctness")
    if isinstance(correctness, dict):
        lines += _render_correctness(correctness)
    return "\n".join(lines) + "\n"
