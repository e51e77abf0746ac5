"""Cluster routing tier: prefix-affinity + SLO-aware replica placement.

The DP front end (``engine/dp_client.py``) historically placed every
admission on the replica with the fewest live requests. That is blind to
the two signals that dominate chat-serving economics at scale:

* **where a conversation's KV already lives** — a session turn placed on
  the replica that prefix-cached the previous turns skips most of its
  prefill (multi-replica prefix reuse, ROADMAP item 3), and
* **how pressured each replica actually is** — queue depth alone misses
  a KV-saturated or latency-degraded replica until it sheds.

``ReplicaRouter`` scores every admission across the alive replicas:

``affinity(req, r)``
    Fraction of the request's leading prompt pages whose chained
    ``BlockHash`` (same sha256 page-chain scheme as
    ``core/block_pool.py``, so equal hashes imply equal full prefixes)
    is present in replica ``r``'s *prefix-residency index* — a bounded
    per-replica LRU of page hashes fed by the owner bookkeeping the
    balancer already maintains (registered at admission, extended with
    the generated tokens at finish, dropped wholesale on failover,
    halved under replica eviction pressure, TTL-expired otherwise).
    With hierarchical KV tiering on (core/kv_tier.py), entries are
    additionally TAGGED BY TIER — the replica's drained tier-transition
    stream rides its existing kv_tier stats entry through
    ``observe_stats`` — and each matched page scores its restore-cost
    credit (``TIER_CREDITS``: HBM 1.0, host RAM 0.8, disk 0.55), so a
    returning session routes to the replica that can *restore* its
    prefix cheapest, not only one still holding it in HBM.
    The index is a HINT: a false positive only costs the prefill the
    old balancer would have paid anyway — each replica's own block
    pool re-verifies every page hash before reuse.

``cost(r) = 0.5*queue(r) + 0.3*kv(r) + 0.2*wait(r) - affinity(req, r)``
    ``queue`` is the live front-end request count plus the replica's
    scheduler waiting queue, normalized by ``max_num_seqs``; ``kv`` is
    the replica's block-pool usage fraction; ``wait`` is the mean
    device-wait step phase (the PR5 step-phase profiler) normalized to
    a 0.5 s ceiling. The load terms come from the replica's existing
    ``get_stats`` RPC on a short TTL (``VDT_ROUTER_STATS_TTL_S``):
    in-process replicas refresh synchronously on the admission path,
    subprocess replicas are fed passively by the server's periodic
    stats polls — the router never opens a new channel.

Guard rails:

* **Spillover** — a replica whose blended pressure
  ``max(kv, min(queue, 1))`` exceeds ``VDT_ROUTER_SPILL_PRESSURE``
  forfeits its affinity credit, so a hot home replica spills session
  turns to the least-cost healthy replica instead of melting down.
* **Stale-stats degradation** — when every alive replica's snapshot is
  older than ``VDT_ROUTER_STALE_S`` (or the ``router.stale_stats``
  fault point is armed), the router ignores affinity AND the stale
  load terms and falls back to pure least-live-count balancing:
  affinity on blind load signals would herd a session-heavy workload
  onto one replica with nothing to push back.
* **Kill switch** — ``VDT_ROUTER=0`` removes the router entirely;
  the balancer reverts to the pre-router round-robin heuristic.

Telemetry rides the DP stats aggregation as the ``router`` entry
(rendered as the ``vdt:router_*`` families): routed / affinity-hit /
spillover / stale-degradation counters plus per-replica residency-index
occupancy.
"""

import time
from collections import OrderedDict
from typing import Optional

from vllm_distributed_tpu.core.kv_cache_utils import hash_block_tokens
from vllm_distributed_tpu.logger import init_logger
from vllm_distributed_tpu.request import EngineCoreRequest
from vllm_distributed_tpu.utils import fault_injection

logger = init_logger(__name__)

# Pressure above which a replica's residency index is halved: the
# replica's block pool is evicting prefix pages, so half our hints there
# are already dead weight.
_EVICTION_PRESSURE = 0.95
# Tier-aware affinity credit per matched page (core/kv_tier.py tier
# codes): a prefix the replica still holds in HBM is free to reuse, a
# host-RAM-tiered page costs one PCIe scatter, a disk-tiered page a
# file read + decode + scatter — all far cheaper than recomputing the
# prefill, which is what a miss costs. The credits ARE the restore-cost
# model: a returning session routes to the replica that can restore its
# prefix cheapest, not only one still holding it in HBM.
TIER_CREDITS = {0: 1.0, 1: 0.8, 2: 0.55}
# Normalization ceiling (seconds) for the mean device-wait step phase.
_WAIT_CEILING_S = 0.5
# Cost margin below which two replicas tie and the rotation cursor
# decides (keeps placement fair when signals are indistinguishable).
_TIE_EPS = 1e-9


class ReplicaRouter:
    """Placement brain for ``DPEngineClient``. Routing/bookkeeping
    calls run under the balancer's RLock; ``observe_stats`` may arrive
    from the stats-poll thread instead, so it sticks to GIL-atomic
    container operations (plain assignments, OrderedDict get/pop/
    popitem — never iteration over a live index), which is why no
    internal lock is needed."""

    def __init__(self, num_replicas: int, config) -> None:
        from vllm_distributed_tpu import envs
        self.n = num_replicas
        self.block_size = config.cache_config.block_size
        self.max_num_seqs = max(1, config.scheduler_config.max_num_seqs)
        self.stats_ttl_s = envs.VDT_ROUTER_STATS_TTL_S
        self.stale_s = envs.VDT_ROUTER_STALE_S
        self.prefix_pages = envs.VDT_ROUTER_PREFIX_PAGES
        self.prefix_capacity = envs.VDT_ROUTER_PREFIX_CAPACITY
        self.prefix_ttl_s = envs.VDT_ROUTER_PREFIX_TTL_S
        self.spill_pressure = envs.VDT_ROUTER_SPILL_PRESSURE
        # Per-replica prefix-residency index: page hash -> (last touch
        # (monotonic), tier code 0=device/1=host/2=disk). OrderedDict
        # in LRU order (oldest first).
        self._residency: list["OrderedDict[bytes, tuple]"] = [
            OrderedDict() for _ in range(num_replicas)
        ]
        # Per-replica load snapshot + fetch instant (monotonic).
        self._stats: list[dict] = [{} for _ in range(num_replicas)]
        self._stats_at: list[float] = [float("-inf")] * num_replicas
        # Device-wait latency signal, computed as the INTERVAL mean
        # between consecutive snapshots of the cumulative step-phase
        # histogram: the lifetime mean of a long-lived replica barely
        # moves when a slowdown starts, the interval mean tracks it.
        self._wait_prev: list[tuple[float, int]] = \
            [(0.0, 0)] * num_replicas
        self._wait_interval_s: list[float] = [0.0] * num_replicas
        self._rr = 0  # tie-break rotation cursor
        # Decision record of the last route() call (request id, hashes,
        # affinity home, degraded flag), consumed by the on_admit()
        # that follows under the same balancer lock: the counters
        # commit against the replica the request ACTUALLY landed on
        # (a failover retry re-routes, a coordinator can override the
        # pick), and the page-chain sha256 is never paid twice.
        self._pending_route: Optional[dict] = None
        # Counters surfaced as vdt:router_* (exact values — one router
        # instance owns the whole fleet's placement, nothing to merge).
        self.requests_routed = 0
        self.affinity_hits = 0
        self.spillovers = 0
        self.stale_degradations = 0

    # ------------------------------------------------------------------
    # Prefix hashing (same page-chain scheme as the block pool)
    # ------------------------------------------------------------------
    def _page_hashes(self, token_ids: list[int]) -> list[bytes]:
        """Chained page hashes of the leading ``prefix_pages`` full
        pages of ``token_ids`` (page granularity = cache block size)."""
        hashes: list[bytes] = []
        parent: Optional[bytes] = None
        limit = min(len(token_ids) // self.block_size, self.prefix_pages)
        for p in range(limit):
            chunk = tuple(
                token_ids[p * self.block_size:(p + 1) * self.block_size])
            parent = hash_block_tokens(parent, chunk).hash_value
            hashes.append(parent)
        return hashes

    def request_hashes(self, request: EngineCoreRequest) -> list[bytes]:
        """Affinity key for one admission. Multimodal prompts are
        skipped: their block hashes are salted with the image content
        hash scheduler-side, and recomputing that salt at the front end
        would hash the full embeds per admission — the affinity hint is
        not worth that cost."""
        if request.mm_inputs:
            return []
        return self._page_hashes(request.prompt_token_ids)

    # ------------------------------------------------------------------
    # Residency index bookkeeping (fed by the balancer's owner state)
    # ------------------------------------------------------------------
    def _register(self, replica: int, hashes: list[bytes],
                  tier: int = 0) -> None:
        if not hashes:
            return
        index = self._residency[replica]
        now = time.monotonic()
        for h in hashes:
            index.pop(h, None)
            index[h] = (now, tier)  # most-recently-used position
        while len(index) > self.prefix_capacity:
            index.popitem(last=False)

    # -- Tier transitions (core/kv_tier.py feed via observe_stats) -----
    def on_demote(self, replica: int, hashes: list[bytes],
                  tier: int) -> None:
        """Pages left the replica's device pool for a spill tier (or
        came back: tier 0 = promoted to HBM). Entries we track retag
        in place — keeping their recency — so affinity scores the
        RESTORE cost instead of pretending the prefix is still free
        (or gone). Hashes we never indexed are ignored: the feed is a
        hint stream, not an index bootstrap."""
        index = self._residency[replica]
        for h in hashes:
            at = index.get(h)
            if at is not None:
                index[h] = (at[0], tier)

    def on_evict(self, replica: int, hashes: list[bytes]) -> None:
        """Pages fell off the replica's last tier: drop the hints."""
        index = self._residency[replica]
        for h in hashes:
            index.pop(h, None)

    def observe_tier_transitions(self, replica: int,
                                 transitions) -> None:
        """Apply one drained (hash hex, tier code) transition stream
        from the replica's kv_tier stats entry (rides the existing
        get_stats feed — see observe_stats)."""
        if not transitions:
            return
        for entry in transitions:
            try:
                hex_key, code = entry
                key = bytes.fromhex(hex_key)
            except (TypeError, ValueError):
                continue
            if code < 0:
                self.on_evict(replica, [key])
            else:
                self.on_demote(replica, [key], int(code))

    def on_admit(self, request: EngineCoreRequest, replica: int,
                 hashes: Optional[list[bytes]] = None) -> None:
        """The request landed on ``replica``: its prompt pages will be
        resident there (written during prefill, prefix-cached after).
        Commits the pending route() decision's counters against the
        LANDING replica — exactly once per admission however many
        route() retries a failover cost, and honestly when a
        coordinator overrode the pick — and reuses its hashes instead
        of paying the page chain twice."""
        pend = self._pending_route
        if (pend is not None
                and pend["rid"] == request.request_id):
            self._pending_route = None
            if hashes is None:
                hashes = pend["hashes"]
            self.requests_routed += 1
            if pend["degraded"]:
                self.stale_degradations += 1
            elif self._affinity(replica, hashes) > 0.0:
                self.affinity_hits += 1
            elif (pend["home"] is not None
                  and pend["home"] != replica
                  and pend["home_pressured"]):
                # The guard rail fired: a home held this prefix but
                # its pressure forfeited the credit. (A home merely
                # losing on cost is ordinary placement, not spillover.)
                self.spillovers += 1
        if hashes is None:
            hashes = self.request_hashes(request)
        self._register(replica, hashes)

    def on_finish(self, request: EngineCoreRequest,
                  generated: list[int], replica: int) -> None:
        """A finished request leaves its FULL sequence prefix-cached on
        its replica — the next session turn's prompt extends it, so
        indexing prompt+generated gives that turn page-exact affinity."""
        if request.mm_inputs:
            return
        tokens = list(request.prompt_token_ids) + list(generated or [])
        self._register(replica, self._page_hashes(tokens))

    def on_replica_down(self, replica: int) -> None:
        """Failover: the replica's KV pool is gone with it; journaled
        sessions re-home as their migrated continuations re-admit."""
        self._residency[replica].clear()
        self._stats[replica] = {}
        self._stats_at[replica] = float("-inf")
        self._wait_prev[replica] = (0.0, 0)
        self._wait_interval_s[replica] = 0.0

    def reset(self) -> None:
        """Full-fleet restart: every pool respawned empty."""
        for i in range(self.n):
            self.on_replica_down(i)

    def grow(self, count: int = 1) -> None:
        """Elastic scale-out (engine/fleet.py): extend every per-replica
        array with empty state for ``count`` appended replicas. The new
        slots start cold (no residency, stats at -inf), exactly like a
        replica that just came back from on_replica_down."""
        for _ in range(count):
            self._residency.append(OrderedDict())
            self._stats.append({})
            self._stats_at.append(float("-inf"))
            self._wait_prev.append((0.0, 0))
            self._wait_interval_s.append(0.0)
        self.n += count

    def _affinity(self, replica: int, hashes: list[bytes]) -> float:
        """Tier-weighted matched leading pages / hashed pages, honoring
        the entry TTL (expired entries are pruned as they are seen). A
        device-resident page scores full credit; host/disk-tiered
        pages score their restore-cost discount (TIER_CREDITS), so two
        replicas holding the same prefix in different tiers rank by
        how cheaply each can actually serve it."""
        if not hashes:
            return 0.0
        index = self._residency[replica]
        now = time.monotonic()
        credit = 0.0
        for h in hashes:
            at = index.get(h)
            if at is None:
                break
            ts, tier = at
            if now - ts > self.prefix_ttl_s:
                index.pop(h, None)
                break
            credit += TIER_CREDITS.get(tier, TIER_CREDITS[2])
        return credit / len(hashes)

    # ------------------------------------------------------------------
    # Load snapshots (existing get_stats RPC, short TTL)
    # ------------------------------------------------------------------
    def observe_stats(self, replica: int, stats: dict) -> None:
        """Feed one replica's stats dict (passively from the server's
        periodic polls, or from a synchronous in-process refresh)."""
        if not isinstance(stats, dict):
            return
        if ("num_running_reqs" not in stats
                and "kv_cache_usage" not in stats):
            # Not a scheduler stats dict (the generic utility fan-out
            # aggregates other dict-shaped RPC results through the same
            # path): don't let it overwrite a real load snapshot.
            return
        self._stats[replica] = stats
        self._stats_at[replica] = time.monotonic()
        phases = stats.get("step_phase_seconds")
        # device_wait: the one blocking phase that both the synchronous
        # and the batch-queue step observe ("wait" is the latter's alone).
        h = phases.get("device_wait") if isinstance(phases, dict) else None
        if isinstance(h, dict) and h.get("count"):
            s, c = float(h.get("sum", 0.0)), int(h["count"])
            ps, pc = self._wait_prev[replica]
            if c > pc:
                self._wait_interval_s[replica] = (s - ps) / (c - pc)
            elif c < pc:
                # Counter went backwards: the replica restarted with a
                # fresh histogram — restart the interval baseline.
                self._wait_interval_s[replica] = 0.0
            self._wait_prev[replica] = (s, c)
        # Tier transitions (hierarchical KV memory): the replica's
        # kv_tier stats entry carries a drained (hash, tier) stream —
        # demoted pages retag to their restore-cost tier, tier-evicted
        # pages drop, promoted pages regain full device credit.
        kv_tier = stats.get("kv_tier")
        if isinstance(kv_tier, dict):
            self.observe_tier_transitions(
                replica, kv_tier.get("transitions"))
        if (float(stats.get("kv_cache_usage", 0.0)) >= _EVICTION_PRESSURE
                and self._residency[replica]):
            # The replica is evicting prefix pages; drop the oldest half
            # of our hints about it instead of advertising dead pages.
            index = self._residency[replica]
            for _ in range(len(index) // 2):
                try:
                    index.popitem(last=False)
                except KeyError:  # raced a TTL prune on the route path
                    break

    def maybe_refresh(self, clients: list, down: set) -> None:
        """Refresh expired snapshots where it costs nothing: in-process
        replicas answer get_stats inline (a dict build). Subprocess
        replicas are never polled here — their snapshots arrive via
        observe_stats from the pump-thread stats path."""
        if fault_injection.should_fire("router.stale_stats"):
            return  # drill: signals stay frozen until they expire
        now = time.monotonic()
        for i, client in enumerate(clients):
            if i in down or now - self._stats_at[i] < self.stats_ttl_s:
                continue
            if getattr(client, "engine_core", None) is None:
                continue  # subprocess replica: passive feed only
            try:
                # include_events=False: the event-ring drain is
                # destructive and belongs to the real stats poll.
                self.observe_stats(
                    i, client.call_utility("get_stats", False))
            except Exception:  # noqa: BLE001 - replica busy/dying; the
                # snapshot stays stale and the scoring degrades.
                pass

    def _stale(self, alive: list[int]) -> bool:
        now = time.monotonic()
        return all(now - self._stats_at[i] > self.stale_s for i in alive)

    # ------------------------------------------------------------------
    # Scoring
    # ------------------------------------------------------------------
    def _load_terms(self, i: int,
                    live_counts: list[int]) -> tuple[float, float, float]:
        stats = self._stats[i]
        queue = ((live_counts[i]
                  + float(stats.get("num_waiting_reqs", 0.0)))
                 / self.max_num_seqs)
        kv = float(stats.get("kv_cache_usage", 0.0))
        # Interval mean (maintained by observe_stats), not the lifetime
        # histogram mean: a slowdown that starts after hours of serving
        # must still move the signal.
        wait = min(1.0, self._wait_interval_s[i] / _WAIT_CEILING_S)
        return queue, kv, wait

    def pressure(self, i: int, live_counts: list[int]) -> float:
        queue, kv, _ = self._load_terms(i, live_counts)
        return max(kv, min(queue, 1.0))

    def route(self, request: Optional[EngineCoreRequest],
              live_counts: list[int], down: set,
              pool: Optional[list[int]] = None,
              least_loaded: bool = False) -> int:
        """Pick the replica with the best expected outcome for this
        admission. Caller guarantees at least one replica is alive.
        Counters do NOT move here — the decision record is stashed and
        committed by on_admit() against the landing replica (a failover
        retry re-enters here; a coordinator may override the pick).

        ``pool`` restricts candidates to a replica subset — the disagg
        tier's two-stage placement (engine/disagg.py): prefill-pool
        admissions additionally pass ``least_loaded=True`` (affinity
        buys nothing on a pool whose pages leave with the pull), while
        the decode-home pick at handoff time scores the decode pool
        with the full prefix-affinity + load blend."""
        members = (set(pool) if pool is not None
                   else set(range(self.n)))
        alive = [i for i in range(self.n)
                 if i not in down and i in members]
        assert alive, "route() with every candidate replica down"
        rid = request.request_id if request is not None else None
        if least_loaded or self._stale(alive):
            # Least-live-count with rotation tie-break: the explicit
            # two-stage prefill placement, or the degraded stale-stats
            # mode (identical to the pre-router balancer).
            best = self._least_loaded(alive, live_counts)
            self._rr = (best + 1) % self.n
            self._pending_route = {"rid": rid, "hashes": [],
                                   "degraded": not least_loaded,
                                   "home": None,
                                   "home_pressured": False}
            return best
        hashes = (self.request_hashes(request)
                  if request is not None else [])
        best, best_cost = None, None
        home, home_aff, home_pressured = None, 0.0, False
        for off in range(self.n):
            i = (self._rr + off) % self.n
            if i in down or i not in members:
                continue
            queue, kv, wait = self._load_terms(i, live_counts)
            affinity = self._affinity(i, hashes)
            pressured = max(kv, min(queue, 1.0)) > self.spill_pressure
            if affinity > home_aff:
                home, home_aff, home_pressured = i, affinity, pressured
            if pressured:
                # Pressured replicas forfeit their affinity credit so a
                # hot home spills instead of melting down.
                affinity = 0.0
            cost = 0.5 * queue + 0.3 * kv + 0.2 * wait - affinity
            if best_cost is None or cost < best_cost - _TIE_EPS:
                best, best_cost = i, cost
        self._rr = (best + 1) % self.n
        self._pending_route = {"rid": rid, "hashes": hashes,
                               "degraded": False, "home": home,
                               "home_pressured": home_pressured}
        return best

    def _least_loaded(self, alive: list[int],
                      live_counts: list[int]) -> int:
        best, best_load = None, None
        for off in range(self.n):
            i = (self._rr + off) % self.n
            if i not in alive:
                continue
            if best_load is None or live_counts[i] < best_load:
                best, best_load = i, live_counts[i]
        return best

    # ------------------------------------------------------------------
    def get_stats(self) -> dict:
        """Telemetry entry attached to the DP stats aggregation and
        rendered as the vdt:router_* families."""
        return {
            "requests_routed": self.requests_routed,
            "affinity_hits": self.affinity_hits,
            "spillovers": self.spillovers,
            "stale_degradations": self.stale_degradations,
            "prefix_index_entries": [len(x) for x in self._residency],
        }
