"""Correctness sentinel (``VDT_CORRECTNESS``): fleet canary probes,
in-flight numerics watch, and auto-quarantine signals.

The serving stack routes hot paths through a dozen lossy-or-risky
mechanisms behind default-off flags — quantized collectives, the fused
block mega-kernel, TPLA latent sharding, tiered KV spill/restore,
disagg handoff, fleet warm starts. All of them are contractually
token-invisible, and none of them were *watched*: a corrupted spill
file or a bad HBM replica after a warm start would surface only as
user complaints. This module is the detector. Three mechanisms, one
suspicion ladder:

* **Canary probes** — the DP client's maintenance tick periodically
  fans one pinned greedy golden prompt out to every in-rotation
  replica (``VDT_CANARY_INTERVAL_S``). Canaries ride the REAL serving
  path (same wire, same scheduler, same kernels) but are marked
  best-effort (priority 1) under the reserved ``_canary`` tenant, so
  the QoS layer never charges them to anyone's quota, the SLO scorer
  never sees them (their outputs are absorbed here, before the output
  processor), and the failover journal never migrates them (a canary
  must pin to the replica it probes). Each completed round compares
  every replica's token output + final-position logprob fingerprint
  against a content-addressed **reference journal**: the key is a
  sha256 over (prompt ids, sampling knobs, flag-config fingerprint),
  so fleets under different kernel flags self-seed DISJOINT
  references and a flag flip can never masquerade as corruption. The
  first unanimous round seeds the journal; after that any replica that
  strays diverges with cause ``reference`` (tokens) or ``logprob``
  (fingerprint drifted past tolerance).

* **Cross-replica voting** — the same round majority-votes the
  replicas against each other, which catches single-replica corruption
  the journal cannot *date* (a reference seeded from an already-bad
  majority is wrong forever; a vote is wrong only while the bad
  replicas outnumber the good). A minority replica diverges with cause
  ``vote`` and climbs the suspicion ladder. A FLEET-WIDE reference
  mismatch (every replica agrees, journal disagrees) counts a
  divergence per replica but suspects nobody — there is no odd one
  out to isolate, only an operator-visible signal.

* **Numerics watch** — the model runner's pre-sampling tap
  (:class:`NumericsTap`) reduces each step's logits to three scalars
  on device (non-finite count, mean entropy, mean top-1/top-2 margin)
  and feeds rolling histograms (``vdt:logits_entropy``,
  ``vdt:logits_top_margin``) plus a NaN counter per replica. The
  front-end drift detector compares each replica's rolling entropy
  window against the fleet mean (``VDT_NUMERICS_DRIFT_FRAC``); NaNs
  and sustained drift climb a second strike ladder.

Either ladder reaching ``VDT_CANARY_QUARANTINE_N`` hardens into a
**replica-quarantine hint** consumed by the fleet controller under
``VDT_FLEET_SIGNALS`` — drain + respawn through the PR-16 force-cycle
machinery, never a new actuation path. ``VDT_CORRECTNESS=0`` (the
default) constructs nothing: no injector, no tap, no new stats keys,
old wire bytes.
"""

import hashlib
import time
from collections import Counter, deque
from typing import Optional

from vllm_distributed_tpu.core.sched.qos import CANARY_TENANT
from vllm_distributed_tpu.logger import init_logger
from vllm_distributed_tpu.metrics import events as ev
from vllm_distributed_tpu.metrics.stats import Histogram
from vllm_distributed_tpu.request import EngineCoreRequest
from vllm_distributed_tpu.sampling_params import SamplingParams
from vllm_distributed_tpu.utils import fault_injection

logger = init_logger(__name__)

# Canary request ids: "<prefix><round>-r<replica>". The prefix is the
# ownership test on the output path (absorbed before any front-end
# bookkeeping), so it must never collide with user request ids.
CANARY_PREFIX = "vdt-canary-"
# Decode length of every probe: long enough that a single corrupted
# page or a drifted logit actually lands in the compared window, short
# enough to be noise on a serving replica.
CANARY_DECODE_TOKENS = 8
# |logprob - reference| above this is a fingerprint divergence even
# with identical tokens (catches quality drift below the argmax).
CANARY_LOGPROB_TOL = 0.05
# A round that hasn't fully resolved after this many intervals is
# expired: responders are scored, silent replicas diverge as "timeout"
# (if at least one replica DID answer — a globally idle fleet is the
# wedge detector's problem, not a correctness signal).
CANARY_ROUND_TIMEOUT_INTERVALS = 4.0
# Pinned golden prompts (token ids — canaries are injected below the
# tokenizer). Small ids exist in every vocabulary; each round rotates
# so a position-dependent corruption can't hide behind one prompt.
GOLDEN_PROMPTS = (
    (11, 29, 7, 3, 17, 23, 5, 13),
    (2, 71, 41, 19, 31, 59, 43, 37),
    (89, 13, 61, 47, 83, 5, 67, 53),
    (73, 79, 3, 97, 11, 2, 19, 29),
)

# Rolling numerics window (per-step means) the drift detector compares
# against the fleet aggregate.
NUMERICS_WINDOW = 128
# The tap re-derives logits from the sampled hidden rows (an extra
# lm-head matmul), so it samples every Nth decode step instead of all
# of them — real numerics poison (a NaN'd KV page, a biased unit)
# persists across steps, so a strided watch still catches it while
# bounding the steady-state cost to ~1/N of a logits pass.
NUMERICS_TAP_STRIDE = 16
ENTROPY_BUCKETS = (0.125, 0.25, 0.5, 1.0, 2.0, 4.0, 8.0, 16.0)
MARGIN_BUCKETS = (0.125, 0.25, 0.5, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0)


def canary_sampling_params() -> SamplingParams:
    """Pinned greedy knobs: temperature 0 (argmax — replicas serving
    the same weights MUST agree), fixed decode length, eos ignored so
    length never varies, chosen-token logprobs for the fingerprint."""
    return SamplingParams(temperature=0.0, max_tokens=CANARY_DECODE_TOKENS,
                          ignore_eos=True, logprobs=1)


def flag_config_fingerprint() -> str:
    """Hash of the full VDT flag configuration (minus the sentinel's
    own knobs): the reference-journal key component that keeps
    references taken under different kernel flags from ever crossing
    (VDT_FUSED_KV_WRITE on against off). Over-keying
    is safe — an unrelated flag flip merely re-seeds."""
    from vllm_distributed_tpu import envs
    parts = []
    for name in sorted(envs.environment_variables):
        if name.startswith(("VDT_CORRECTNESS", "VDT_CANARY",
                            "VDT_NUMERICS")):
            continue
        try:
            parts.append(f"{name}={envs.environment_variables[name]()!r}")
        except Exception:  # noqa: BLE001 - malformed env value; the
            # component reading it will raise on ITS read. Key on the
            # raw text so the fingerprint still separates configs.
            import os
            parts.append(f"{name}={os.getenv(name)!r}")
    return hashlib.sha256(";".join(parts).encode()).hexdigest()[:16]


def reference_key(prompt: tuple, sp: SamplingParams, flag_fp: str) -> str:
    """Content address of one golden prompt's reference entry."""
    text = (f"{flag_fp}|{list(prompt)}|t={sp.temperature}"
            f"|n={sp.max_tokens}|lp={sp.logprobs}")
    return hashlib.sha256(text.encode()).hexdigest()[:24]


class NumericsTap:
    """Per-replica pre-sampling numerics watch, host side. The model
    runner dispatches a tiny jitted reduction over the SAME gathered
    hidden rows the sampler consumes (one extra LM-head matmul per
    step — the plane's documented cost) and hands the device array
    here; the harvest runs one step behind so the tap never blocks the
    dispatch path. Constructed only under VDT_CORRECTNESS."""

    def __init__(self) -> None:
        self.nan_steps = 0
        self.entropy = Histogram(ENTROPY_BUCKETS)
        self.top_margin = Histogram(MARGIN_BUCKETS)
        self._window: deque = deque(maxlen=NUMERICS_WINDOW)
        self._pending = None

    def dispatch(self, dev) -> None:
        """Queue one step's [nonfinite, mean_entropy, mean_margin]
        device reduction; harvests the previous step's first."""
        self.harvest()
        self._pending = dev

    def harvest(self) -> None:
        dev, self._pending = self._pending, None
        if dev is None:
            return
        import numpy as np
        try:
            arr = np.asarray(dev)
        except Exception:  # noqa: BLE001 - a poisoned step (device
            # error) must not take the stats path down with it; the
            # step's own fetch surfaces the failure.
            return
        nonfinite = float(arr[0])
        if fault_injection.should_fire("numerics.nan_inject"):
            # Drill: a single NaN landed in this step's logits.
            nonfinite += 1.0
        if nonfinite > 0.0 or not np.isfinite(arr[1:]).all():
            self.nan_steps += 1
            return  # poisoned step: entropy/margin means are garbage
        self.entropy.observe(float(arr[1]))
        self.top_margin.observe(float(arr[2]))
        self._window.append(float(arr[1]))

    def stats(self) -> dict:
        """Flat per-replica entry for the runner's get_stats (the DP
        aggregator maps it per replica index — never summed)."""
        self.harvest()
        window = list(self._window)
        return {
            "nan_steps": self.nan_steps,
            "entropy": self.entropy.to_dict(),
            "top_margin": self.top_margin.to_dict(),
            "entropy_window_mean": (sum(window) / len(window)
                                    if window else None),
            "window_steps": len(window),
        }


class CorrectnessPlane:
    """Front-end correctness sentinel: canary round state machine,
    reference journal, vote, numerics drift, and the suspicion ladders
    that feed the fleet's quarantine hints. Owned by the DP client and
    driven from its maintenance tick under the balancer lock — no
    internal locking needed."""

    def __init__(self, events: Optional[ev.EventRecorder] = None) -> None:
        from vllm_distributed_tpu import envs
        self.interval_s = envs.VDT_CANARY_INTERVAL_S
        self.quarantine_n = envs.VDT_CANARY_QUARANTINE_N
        self.drift_frac = envs.VDT_NUMERICS_DRIFT_FRAC
        self.events = events if events is not None else ev.EventRecorder()
        self.sampling = canary_sampling_params()
        self.flag_fp = flag_config_fingerprint()
        # Reference journal: content address -> {"tokens", "lp"}.
        self.journal: dict[str, dict] = {}
        # Round state: replica -> {"tokens": [...], "lp": float|None,
        # "done": bool}; None between rounds.
        self._round: Optional[dict[int, dict]] = None
        self._round_idx = 0
        # Round id the in-flight probes were minted under: outputs
        # from an EXPIRED round can still stream in after the next
        # round opened (probes are never aborted — they finish on
        # their own token budget) and must not pollute its slots.
        self._round_id = -1
        self._round_started = float("-inf")
        self._round_deadline = 0.0
        self._round_key = ""
        # Counters (exact — one plane owns the fleet's canaries).
        self.probes: dict[int, int] = {}
        self.divergences: dict[int, dict[str, int]] = {}
        # Suspicion ladders: consecutive divergent canary rounds and
        # consecutive bad numerics observations, per replica. Either
        # reaching quarantine_n emits ONE hint per episode.
        self._canary_strikes: dict[int, int] = {}
        self._numerics_strikes: dict[int, int] = {}
        self._suspect: dict[int, int] = {}
        self._hinted: set[int] = set()
        self._pending_hints: dict[int, str] = {}
        self.quarantine_hints_emitted = 0
        # Numerics drift bookkeeping: replica -> last seen nan_steps.
        self._last_nan: dict[int, int] = {}
        logger.info(
            "correctness sentinel on: %d golden prompts every %.1fs, "
            "quarantine after %d strikes, flag fingerprint %s",
            len(GOLDEN_PROMPTS), self.interval_s, self.quarantine_n,
            self.flag_fp)

    # ------------------------------------------------------------------
    # Canary rounds
    # ------------------------------------------------------------------
    def owns(self, req_id: str) -> bool:
        return req_id.startswith(CANARY_PREFIX)

    def due_probes(self, targets: list[int],
                   now: Optional[float] = None) -> list[tuple]:
        """(replica, EngineCoreRequest) pairs to submit this tick —
        empty while a round is in flight or the interval hasn't
        elapsed. ``targets`` is the in-rotation replica set."""
        if now is None:
            now = time.monotonic()
        if self._round is not None:
            if now < self._round_deadline:
                return []
            self._expire_round()
        if now - self._round_started < self.interval_s or not targets:
            return []
        prompt = GOLDEN_PROMPTS[self._round_idx % len(GOLDEN_PROMPTS)]
        self._round_key = reference_key(prompt, self.sampling,
                                        self.flag_fp)
        self._round = {
            i: {"tokens": [], "lp": None, "done": False} for i in targets
        }
        self._round_started = now
        self._round_deadline = now + max(
            1.0, CANARY_ROUND_TIMEOUT_INTERVALS * max(self.interval_s, 1.0))
        rid_round = self._round_idx
        self._round_id = rid_round
        self._round_idx += 1
        out = []
        for i in targets:
            rid = f"{CANARY_PREFIX}{rid_round}-r{i}"
            req = EngineCoreRequest(
                request_id=rid,
                prompt_token_ids=list(prompt),
                sampling_params=canary_sampling_params(),
                priority=1,  # best-effort: shed/preempted first
                tenant=CANARY_TENANT,  # QoS-exempt reserved bucket
            )
            if ev.trace_plane_enabled():
                # A divergence links straight to its Perfetto trace.
                from vllm_distributed_tpu import trace_plane
                req.trace_ctx = trace_plane.mint_trace_ctx(rid)
            out.append((i, req))
        return out

    def on_submit_failed(self, req_id: str) -> None:
        """The replica refused the canary (mid-death): drop it from the
        round so the survivors still resolve."""
        i = self._replica_of(req_id)
        if self._round is not None and i in self._round:
            del self._round[i]
            self._maybe_resolve()

    def on_output(self, out) -> None:
        """Absorb one canary EngineCoreOutput (called from the DP
        client's output path, lock held). Canary outputs never reach
        the output processor — that is what keeps them out of SLO
        scoring and front-end stats."""
        i = self._replica_of(out.req_id)
        if (self._round is None or i not in self._round
                or self._round_of(out.req_id) != self._round_id):
            return  # stale round (expired, or a restarted replica)
        slot = self._round[i]
        tokens = list(out.new_token_ids or [])
        if tokens and fault_injection.should_fire("canary.flip_token"):
            # Drill: one replica's canary output perturbed in flight
            # (absorb order is fixed, so rate 0.5 on a 2-replica fleet
            # always corrupts the same replica).
            tokens = [t + 1 for t in tokens]
        slot["tokens"].extend(tokens)
        if out.logprobs:
            last = out.logprobs[-1]
            if isinstance(last, dict) and slot["tokens"]:
                lp = last.get(slot["tokens"][-1])
                if lp is not None:
                    slot["lp"] = float(lp)
        if out.finished:
            slot["done"] = True
            self.probes[i] = self.probes.get(i, 0) + 1
            self._maybe_resolve()

    def _replica_of(self, req_id: str) -> Optional[int]:
        try:
            return int(req_id.rsplit("-r", 1)[1])
        except (IndexError, ValueError):
            return None

    def _round_of(self, req_id: str) -> Optional[int]:
        try:
            return int(req_id[len(CANARY_PREFIX):].split("-", 1)[0])
        except (IndexError, ValueError):
            return None

    def _maybe_resolve(self) -> None:
        if self._round and all(s["done"] for s in self._round.values()):
            done, self._round = self._round, None
            self._resolve(done)

    def _expire_round(self) -> None:
        done, self._round = self._round, None
        responders = {i: s for i, s in done.items() if s["done"]}
        if not responders:
            # Globally idle/stuck fleet: no correctness signal at all —
            # liveness is the wedge detector's ladder, not ours.
            return
        for i in set(done) - set(responders):
            self._diverge(i, "timeout")
        self._resolve(responders)

    # -- Scoring --------------------------------------------------------
    def _resolve(self, round_state: dict[int, dict]) -> None:
        key = self._round_key
        results = {i: (tuple(s["tokens"]), s["lp"])
                   for i, s in round_state.items()}
        votes = Counter(tokens for tokens, _ in results.values())
        majority_tokens, majority_n = votes.most_common(1)[0]
        ref = self.journal.get(key)
        if ref is None and majority_n == len(results):
            # First unanimous round self-seeds the reference.
            lps = [lp for _, lp in results.values() if lp is not None]
            self.journal[key] = {
                "tokens": list(majority_tokens),
                "lp": (sum(lps) / len(lps)) if lps else None,
            }
            self._clean_round(results)
            return
        clean: set[int] = set()
        for i, (tokens, lp) in sorted(results.items()):
            if len(results) > 1 and tokens != majority_tokens \
                    and votes[tokens] < majority_n:
                # The vote isolates the odd one out — the strongest
                # signal (it can date corruption the journal predates).
                self._diverge(i, "vote")
            elif ref is not None and tokens != tuple(ref["tokens"]):
                # Tokens stray from the journal. With a majority intact
                # this replica still strayed alone; fleet-wide (every
                # replica agreeing against the journal) nobody is
                # suspected — there is no odd one out to isolate.
                self._diverge(i, "reference",
                              suspect=majority_n < len(results))
            elif (ref is not None and ref.get("lp") is not None
                  and lp is not None
                  and abs(lp - ref["lp"]) > CANARY_LOGPROB_TOL):
                self._diverge(i, "logprob")
            else:
                clean.add(i)
        self._clean_round({i: results[i] for i in clean})

    def _clean_round(self, results: dict) -> None:
        for i in results:
            self._canary_strikes[i] = 0
            if self._numerics_strikes.get(i, 0) == 0:
                self._suspect[i] = 0
                self._hinted.discard(i)

    def _diverge(self, i: int, cause: str, suspect: bool = True) -> None:
        per = self.divergences.setdefault(i, {})
        per[cause] = per.get(cause, 0) + 1
        self.events.record("", ev.CANARY_DIVERGENCE,
                           {"replica": i, "cause": cause})
        logger.warning("correctness: replica %s canary DIVERGED (%s)",
                       i, cause)
        if suspect:
            self._canary_strikes[i] = self._canary_strikes.get(i, 0) + 1
            self._bump_suspicion(i, cause, self._canary_strikes[i])

    # ------------------------------------------------------------------
    # Numerics feed (per stats poll, per replica)
    # ------------------------------------------------------------------
    def observe_numerics(self, per_replica: dict[int, dict]) -> None:
        """Per-replica numerics snapshots from the DP stats merge: NaN
        deltas and rolling-window entropy drift climb the numerics
        strike ladder; a clean poll resets it."""
        means = {i: nd.get("entropy_window_mean")
                 for i, nd in per_replica.items()
                 if isinstance(nd, dict)
                 and isinstance(nd.get("entropy_window_mean"),
                                (int, float))}
        # Median, not mean: a single poisoned replica drags the fleet
        # MEAN toward itself far enough to flag its healthy peers too
        # (3 replicas at 1, 1, 8 put the mean at 3.3 — every replica
        # "drifts"). The median stays with the healthy majority.
        fleet_mean = None
        if means:
            vals = sorted(means.values())
            m = len(vals)
            fleet_mean = (vals[m // 2] if m % 2
                          else 0.5 * (vals[m // 2 - 1] + vals[m // 2]))
        for i, nd in per_replica.items():
            if not isinstance(nd, dict):
                continue
            bad = None
            nan = int(nd.get("nan_steps", 0) or 0)
            if nan > self._last_nan.get(i, 0):
                bad = "nan_logits"
            self._last_nan[i] = nan
            if (bad is None and self.drift_frac > 0
                    and fleet_mean is not None and len(means) > 1
                    and i in means
                    and abs(means[i] - fleet_mean)
                    > self.drift_frac * max(abs(fleet_mean), 1e-6)):
                bad = "numerics_drift"
            if bad is None:
                self._numerics_strikes[i] = 0
                if self._canary_strikes.get(i, 0) == 0 \
                        and self._suspect.get(i):
                    self._suspect[i] = 0
                    self._hinted.discard(i)
                continue
            per = self.divergences.setdefault(i, {})
            per[bad] = per.get(bad, 0) + 1
            self._numerics_strikes[i] = \
                self._numerics_strikes.get(i, 0) + 1
            self._bump_suspicion(i, bad, self._numerics_strikes[i])

    # ------------------------------------------------------------------
    # Suspicion → quarantine
    # ------------------------------------------------------------------
    def _bump_suspicion(self, i: int, cause: str, strikes: int) -> None:
        self._suspect[i] = 1
        if strikes >= self.quarantine_n and i not in self._hinted:
            self._hinted.add(i)
            self._pending_hints[i] = cause
            self.quarantine_hints_emitted += 1
            logger.error(
                "correctness: replica %d QUARANTINE hint (%s, %d "
                "consecutive strikes)", i, cause, strikes)

    def quarantine_hints(self) -> dict[int, str]:
        """Drain pending replica-quarantine hints ({replica: cause}) —
        the fleet controller's VDT_FLEET_SIGNALS feed."""
        hints, self._pending_hints = self._pending_hints, {}
        return hints

    def suspects(self) -> dict[int, int]:
        return {i: v for i, v in sorted(self._suspect.items()) if v}

    def forget_replica(self, i: int) -> None:
        """A replica left rotation (retired or respawned fresh): its
        suspicion history died with it."""
        for store in (self._canary_strikes, self._numerics_strikes,
                      self._suspect, self._last_nan,
                      self._pending_hints):
            store.pop(i, None)
        self._hinted.discard(i)
        if self._round is not None and i in self._round:
            del self._round[i]
            self._maybe_resolve()

    # ------------------------------------------------------------------
    def get_stats(self) -> dict:
        """The ``correctness`` entry of the DP stats aggregation —
        per-replica maps, NEVER numeric-summed across replicas."""
        return {
            "probes": dict(sorted(self.probes.items())),
            "divergences": {i: dict(c) for i, c in
                            sorted(self.divergences.items())},
            "suspects": {i: int(bool(v)) for i, v in
                         sorted(self._suspect.items())},
            "quarantine_hints": self.quarantine_hints_emitted,
            "journal_entries": len(self.journal),
            "rounds": self._round_idx,
            "round_in_flight": self._round is not None,
            "flag_fingerprint": self.flag_fp,
        }
