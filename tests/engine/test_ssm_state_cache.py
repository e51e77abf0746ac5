"""SSM state cache (core/state_cache.py): greedy parity matrix,
eviction/capacity, kill switch, corruption drill, and O(1) crash
recovery.

Acceptance (ISSUE 8): greedy outputs must be token-identical with the
state cache on vs off for mamba AND jamba (hybrid stacks must restore
state rows and attention KV pages coherently); preempt-park-resume must
match the no-preempt run; journal replay of a stateful request must
resume from the last checkpoint, re-prefilling at most
``VDT_SSM_CKPT_INTERVAL`` tokens instead of O(prompt).

At the end, the same through Ling-3.0's two kinds of cache for one
request (state rows beside latent pages; ``models/bailing.py``) at a toy
width. An engine built is what a case here costs (its forward programs
compiled again: JAX caches by function object), so what two tests would
compute alike (the cache-off session, the baseline of the crash drills,
Ling's engine with room for all) is a module's fixture.
"""

import asyncio
import os

import numpy as np
import pytest
import torch
from transformers import JambaConfig, MambaConfig
from transformers import JambaForCausalLM as HFJamba
from transformers import MambaForCausalLM as HFMamba

from vllm_distributed_tpu.core.state_cache import (StateCacheManager,
                                                   journal_path,
                                                   read_journal,
                                                   write_journal)
from vllm_distributed_tpu.engine.arg_utils import EngineArgs
from vllm_distributed_tpu.engine.llm_engine import LLMEngine
from vllm_distributed_tpu.sampling_params import SamplingParams
from vllm_distributed_tpu.utils import fault_injection as fi


@pytest.fixture(scope="module")
def mamba_ckpt(tmp_path_factory):
    torch.manual_seed(0)
    cfg = MambaConfig(vocab_size=128, hidden_size=32, state_size=8,
                      num_hidden_layers=2, conv_kernel=4, expand=2,
                      time_step_rank=4, use_conv_bias=True,
                      use_bias=False, eos_token_id=1)
    hf = HFMamba(cfg)
    path = tmp_path_factory.mktemp("mamba-sc-tiny")
    hf.save_pretrained(path, safe_serialization=True)
    return str(path)


@pytest.fixture(scope="module")
def jamba_ckpt(tmp_path_factory):
    torch.manual_seed(0)
    cfg = JambaConfig(vocab_size=128, hidden_size=32,
                      intermediate_size=64, num_hidden_layers=4,
                      num_attention_heads=4, num_key_value_heads=2,
                      mamba_d_state=8, mamba_d_conv=4, mamba_expand=2,
                      mamba_dt_rank=4, attn_layer_period=4,
                      attn_layer_offset=2, expert_layer_period=2,
                      expert_layer_offset=1, num_experts=4,
                      num_experts_per_tok=2, max_position_embeddings=96,
                      eos_token_id=1, tie_word_embeddings=False,
                      use_mamba_kernels=False)
    hf = HFJamba(cfg)
    path = tmp_path_factory.mktemp("jamba-sc-tiny")
    hf.save_pretrained(path, safe_serialization=True)
    return str(path)


def _make_engine(path, monkeypatch, cache_on=True, interval=8,
                 **overrides):
    # The scheduler/runner read the envs at CONSTRUCTION.
    monkeypatch.setenv("VDT_SSM_STATE_CACHE", "1" if cache_on else "0")
    monkeypatch.setenv("VDT_SSM_CKPT_INTERVAL", str(interval))
    args = dict(model=path, dtype="float32", block_size=4,
                num_gpu_blocks_override=128, max_model_len=96,
                max_num_batched_tokens=64, max_num_seqs=8,
                skip_tokenizer_init=True)
    args.update(overrides)
    return LLMEngine(EngineArgs(**args).create_engine_config())


def _drain(engine, max_steps=500):
    done = {}
    for _ in range(max_steps):
        for out in engine.step():
            if out.finished:
                done[out.request_id] = list(out.outputs[0].token_ids)
        if not engine.has_unfinished_requests():
            break
    return done


def _run_session(engine, tag, turns=3, prompt_len=20, max_tokens=6):
    """Multi-turn chat shape: each turn's prompt extends the previous
    turn's full sequence — the traffic state-snapshot reuse exists for."""
    sp = SamplingParams(temperature=0.0, max_tokens=max_tokens,
                        ignore_eos=True)
    prompt = [(i * 7 + 3) % 128 for i in range(prompt_len)]
    outs = []
    for turn in range(turns):
        engine.add_request(f"{tag}-{turn}", list(prompt), sp)
        done = _drain(engine)
        toks = done[f"{tag}-{turn}"]
        outs.append(toks)
        prompt = prompt + toks + [(turn * 13 + 5) % 128]
    return outs


def _ssm_stats(engine):
    return {k: v for k, v in engine.get_stats().items()
            if k.startswith("ssm_")}


@pytest.fixture(scope="module")
def mamba_off_session(mamba_ckpt):
    """The three-turn session on the cache-off engine with
    ``_make_engine``'s own arguments: its outputs, and the engine."""
    with pytest.MonkeyPatch.context() as mp:
        engine = _make_engine(mamba_ckpt, mp, cache_on=False)
        return _run_session(engine, "off"), engine


# ---------------------------------------------------------------------------
# Greedy parity matrix
# ---------------------------------------------------------------------------
def test_mamba_multi_turn_parity_and_hits(mamba_ckpt, mamba_off_session,
                                          monkeypatch):
    """Turn N+1 resumes from turn N's snapshot; greedy outputs are
    token-identical to the cache-off engine on identical traffic."""
    on_engine = _make_engine(mamba_ckpt, monkeypatch, cache_on=True)
    on = _run_session(on_engine, "on")
    stats = _ssm_stats(on_engine)
    off, off_engine = mamba_off_session
    assert on == off
    assert stats["ssm_state_cache_hits"] >= 2, stats
    assert stats["ssm_resume_tokens_saved"] > 0, stats
    assert stats["ssm_checkpoints"] >= 1, stats
    assert stats["ssm_state_bytes_held"] > 0, stats
    # Kill switch: the cache-off engine runs no state-cache machinery.
    assert off_engine.engine_core.engine_core.scheduler.state_cache \
        is None
    assert not _ssm_stats(off_engine)


def test_mamba_chunked_prefill_snapshot_parity(mamba_ckpt, monkeypatch):
    """A prompt far longer than the interval forces mid-prefill
    snapshots (grant clipping); parity must hold and the second session
    turn resumes deep into the prompt."""
    on_engine = _make_engine(mamba_ckpt, monkeypatch, cache_on=True,
                             max_num_batched_tokens=16)
    on = _run_session(on_engine, "on", turns=2, prompt_len=40)
    stats = _ssm_stats(on_engine)
    off_engine = _make_engine(mamba_ckpt, monkeypatch, cache_on=False,
                              max_num_batched_tokens=16)
    off = _run_session(off_engine, "off", turns=2, prompt_len=40)
    assert on == off
    assert stats["ssm_state_cache_hits"] >= 1, stats
    # The resume skipped at least the first interval boundaries of the
    # 40-token shared prefix.
    assert stats["ssm_resume_tokens_saved"] >= 32, stats


def test_jamba_hybrid_multi_turn_parity(jamba_ckpt, monkeypatch):
    """Hybrid stacks must restore mamba state rows AND attention KV
    pages coherently — token-identical greedy outputs prove both sides
    re-entered at the same boundary."""
    on_engine = _make_engine(jamba_ckpt, monkeypatch, cache_on=True)
    on = _run_session(on_engine, "on")
    stats = _ssm_stats(on_engine)
    off_engine = _make_engine(jamba_ckpt, monkeypatch, cache_on=False)
    off = _run_session(off_engine, "off")
    assert on == off
    assert stats["ssm_state_cache_hits"] >= 1, stats
    # Hybrid hits ride the page prefix cache (forced on): the KV pages
    # of the shared prefix were reused, not recomputed.
    sched = on_engine.engine_core.engine_core.scheduler
    assert sched.kv_cache_manager.enable_caching


def test_jamba_preempt_park_resume_parity(jamba_ckpt, monkeypatch):
    """A page pool too small for the batch forces preemption; parked
    state lets victims resume as continuations, token-identical to the
    cache-off run (which re-prefills from scratch)."""
    def run(cache_on):
        engine = _make_engine(jamba_ckpt, monkeypatch, cache_on=cache_on,
                              interval=4, num_gpu_blocks_override=16,
                              max_model_len=64, max_num_seqs=4)
        sp = SamplingParams(temperature=0.0, max_tokens=16,
                            ignore_eos=True)
        prompts = [[(i * 5 + j) % 128 for j in range(8)]
                   for i in range(4)]
        for i, p in enumerate(prompts):
            engine.add_request(f"r-{i}", p, sp)
        done = _drain(engine)
        stats = engine.get_stats()
        return ([done[f"r-{i}"] for i in range(4)],
                int(stats["num_preemptions"]), _ssm_stats(engine))

    on, preempts_on, stats = run(True)
    off, preempts_off, _ = run(False)
    assert on == off
    assert preempts_on > 0 and preempts_off > 0
    # Parked/periodic snapshots turned at least one resume into a
    # continuation (re-prefill bounded by the interval, not O(seq)).
    assert stats["ssm_state_cache_hits"] >= 1, stats
    assert stats["ssm_resume_tokens_saved"] > 0, stats


def test_mamba_async_scheduling_parity(mamba_ckpt, mamba_off_session,
                                       monkeypatch):
    """Async run-ahead grants snapshot at speculative boundaries whose
    key resolves at commit; a stop before the boundary discards the
    snapshot. Greedy outputs must still match the sync cache-off run."""
    on_engine = _make_engine(mamba_ckpt, monkeypatch, cache_on=True,
                             async_scheduling=True)
    on = _run_session(on_engine, "on")
    stats = _ssm_stats(on_engine)
    off, _ = mamba_off_session
    assert on == off
    assert stats["ssm_state_cache_hits"] >= 1, stats


# ---------------------------------------------------------------------------
# O(1) crash recovery
# ---------------------------------------------------------------------------
PROMPT = [(i * 7 + 3) % 128 for i in range(40)]


def _make_async_engine(path, monkeypatch, tmp_path, cache_on=True):
    from vllm_distributed_tpu.engine.async_llm import AsyncLLM
    monkeypatch.setenv("VDT_SSM_STATE_CACHE", "1" if cache_on else "0")
    monkeypatch.setenv("VDT_SSM_CKPT_INTERVAL", "8")
    monkeypatch.setenv("VDT_SSM_CKPT_DIR", str(tmp_path))
    args = dict(model=path, dtype="float32", block_size=4,
                num_gpu_blocks_override=128, max_model_len=96,
                max_num_batched_tokens=64, max_num_seqs=8,
                skip_tokenizer_init=True,
                restart_backoff_base_s=0.01, restart_backoff_max_s=0.05)
    return AsyncLLM(EngineArgs(**args).create_engine_config(),
                    load_tokenizer=False)


async def _collect(engine, rid, die_after=False):
    sp = SamplingParams(temperature=0.0, max_tokens=20, ignore_eos=True)
    final, first = None, False
    async for out in engine.generate(PROMPT, sp, request_id=rid):
        if not first:
            first = True
            if die_after:
                fi.inject("engine_core.die", max_fires=1)
        final = out
    assert final is not None and final.finished
    return final.outputs[0].token_ids


@pytest.fixture(scope="module")
def crash_baseline(mamba_ckpt, tmp_path_factory):
    """The prompt's 20 tokens from an engine that is never killed."""
    with pytest.MonkeyPatch.context() as mp:
        base = _make_async_engine(mamba_ckpt, mp,
                                  tmp_path_factory.mktemp("base"))
        try:
            return asyncio.run(asyncio.wait_for(
                _collect(base, "base-0"), timeout=120))
        finally:
            base.shutdown()


def test_replay_resumes_from_checkpoint(mamba_ckpt, crash_baseline,
                                        monkeypatch, tmp_path):
    """Kill the core mid-decode: the journaled request replays into the
    respawned core, which resumes from the last host checkpoint — the
    replayed prefill is bounded by VDT_SSM_CKPT_INTERVAL (8), not the
    40-token prompt — and the stream stays token-identical."""
    baseline = crash_baseline
    engine = _make_async_engine(mamba_ckpt, monkeypatch,
                                tmp_path / "rec")
    try:
        resumed = asyncio.run(asyncio.wait_for(
            _collect(engine, "die-0", die_after=True), timeout=180))
        assert resumed == baseline
        assert not engine.errored
        assert engine.output_processor.stats.num_requests_replayed >= 1
        # The FRESH core's stats prove the O(1) resume: the replayed
        # continuation knew >= 41 tokens (prompt + first delivered) and
        # re-prefilled at most one interval past the last checkpoint.
        sc = engine.core.core.scheduler.state_cache
        stats = sc.stats()
        assert stats["ssm_state_cache_hits"] >= 1, stats
        known = len(PROMPT) + 1
        assert stats["ssm_resume_tokens_saved"] >= known - 8, stats
    finally:
        engine.shutdown()


def test_restore_corrupt_degrades_to_reprefill(mamba_ckpt, crash_baseline,
                                               monkeypatch, tmp_path):
    """ssm.restore_corrupt simulates a checksum mismatch on every
    journal read: recovery must degrade to a full re-prefill (counted)
    and stay token-identical."""
    baseline = crash_baseline
    engine = _make_async_engine(mamba_ckpt, monkeypatch,
                                tmp_path / "rec")
    try:
        fi.inject("ssm.restore_corrupt")
        resumed = asyncio.run(asyncio.wait_for(
            _collect(engine, "die-0", die_after=True), timeout=180))
        assert resumed == baseline
        sc = engine.core.core.scheduler.state_cache
        stats = sc.stats()
        assert stats["ssm_restore_corruptions"] >= 1, stats
        assert fi.counters().get("ssm.restore_corrupt", 0) >= 1
    finally:
        fi.clear("ssm.restore_corrupt")
        engine.shutdown()


# ---------------------------------------------------------------------------
# Manager units: eviction/capacity, dedupe, pending lifecycle, journal
# ---------------------------------------------------------------------------
class _Req:
    """Minimal Request stand-in for manager-level tests."""

    def __init__(self, rid, tokens):
        self.request_id = rid
        self.all_token_ids = list(tokens)
        self.mm_hash = None

    @property
    def num_tokens(self):
        return len(self.all_token_ids)


def _mgr(slots=2, interval=4, paged=False, journal_dir=""):
    m = StateCacheManager(num_slots=slots, block_size=4,
                          interval=interval, paged_kv=paged,
                          journal_dir=journal_dir)
    m.bytes_per_slot = 100
    return m


def test_manager_lru_eviction_and_capacity():
    m = _mgr(slots=2)
    reqs = [_Req(f"r{i}", [i * 31 + j for j in range(12)])
            for i in range(3)]
    for r in reqs:
        d = m.maybe_save(r, 4)
        assert d is not None
        m.commit_save(d, r)
    assert m.checkpoints == 3
    assert m.evictions == 1  # r0's snapshot was the LRU victim
    assert len(m.by_key) == 2
    assert m.stats()["ssm_state_bytes_held"] == 200
    # The evicted prefix misses; the survivors hit.
    _, b0, _ = m.get_computed_state(_Req("q0", reqs[0].all_token_ids),
                                    None)
    _, b2, d2 = m.get_computed_state(_Req("q2", reqs[2].all_token_ids),
                                     None)
    assert b0 == 0
    assert b2 == 4 and d2 is not None and d2.slot >= 0
    # Hits count at successful ADMISSION (scheduler-side), not per
    # lookup; bare lookups only tally queries.
    assert m.hits == 0 and m.queries == 2


def test_manager_dedupes_identical_prefixes():
    m = _mgr(slots=4)
    a, b = _Req("a", range(12)), _Req("b", range(12))
    d = m.maybe_save(a, 4)
    m.commit_save(d, a)
    assert m.maybe_save(b, 4) is None  # same content hash: no new slot
    assert len(m.free_slots) == 3


def test_manager_off_boundary_and_pending_abort():
    m = _mgr(slots=2)
    r = _Req("r", range(20))
    assert m.maybe_save(r, 5) is None  # not interval-aligned
    d = m.maybe_save(r, 8)
    assert d is not None and m.is_pending(d)
    # Restart-from-scratch aborts the pending copy: the slot frees and
    # a later commit of the shipped directive is a no-op.
    m.abort_pending("r")
    assert not m.is_pending(d)
    assert len(m.free_slots) == 2
    m.commit_save(d, r)
    assert m.checkpoints == 0 and not m.by_key


def test_manager_speculative_save_commit_validity():
    """An async run-ahead save past the known tokens resolves its key at
    commit; a request that stopped short discards the snapshot."""
    m = _mgr(slots=2)
    r = _Req("r", range(8))  # 8 known tokens
    d = m.maybe_save(r, 12)  # boundary past known: key deferred
    assert d is not None
    m.commit_save(d, r)  # never reached 12 tokens -> discarded
    assert not m.by_key and len(m.free_slots) == 2
    d = m.maybe_save(r, 12)
    r.all_token_ids = list(range(12))  # speculative token committed
    m.commit_save(d, r)
    assert m.checkpoints == 1 and len(m.by_key) == 1


def test_manager_async_save_owes_journal_persist(tmp_path):
    """A speculative (async) save resolves its key at commit, AFTER the
    runner's copy+journal window: the manager owes a persist_only
    directive, pins the slot against eviction until it ships, and
    drains it into the next output."""
    m = _mgr(slots=1, journal_dir=str(tmp_path))
    r = _Req("r", range(8))
    d = m.maybe_save(r, 12)  # key (and journal path) unresolvable
    assert d is not None and d.journal is None
    r.all_token_ids = list(range(12))
    m.commit_save(d, r)
    persists = m.pending_persists
    assert len(persists) == 1 and persists[0].persist_only
    assert persists[0].journal is not None
    # The only slot is journal-pinned: a new save cannot evict it.
    assert m.maybe_save(_Req("x", range(40, 52)), 4) is None
    drained = m.take_persists()
    assert [p.journal for p in drained] == [persists[0].journal]
    assert m.take_persists() == []
    # Unpinned now: the next save may evict it.
    assert m.maybe_save(_Req("x", range(40, 52)), 4) is not None
    assert m.evictions == 1


def test_journal_fingerprint_guards_shared_dirs(tmp_path):
    """A CRC-valid checkpoint written under another model's state
    geometry must miss (and survive — it is someone else's file)."""
    from vllm_distributed_tpu.core.state_cache import state_fingerprint
    arrays = {"conv": np.ones((2, 3, 4), np.float32)}
    m = _mgr(slots=2, journal_dir=str(tmp_path))
    m.journal_fingerprint = state_fingerprint(
        {"conv": (((2, 9, 3, 4)), "float32")})
    r = _Req("r", range(12))
    key = m._key_at(r, 4)
    path = journal_path(str(tmp_path), key)
    write_journal(path, arrays, 4, fingerprint=state_fingerprint(
        {"conv": (((2, 9, 9, 9)), "bfloat16")}))
    _, boundary, _ = m.get_computed_state(r, None)
    assert boundary == 0
    assert os.path.exists(path)  # foreign file NOT quarantined
    assert m.restore_corruptions == 0
    # Matching fingerprint: the same file becomes a hit.
    write_journal(path, arrays, 4,
                  fingerprint=m.journal_fingerprint)
    _, boundary, d = m.get_computed_state(r, None)
    assert boundary == 4 and d.slot == -1 and d.arrays is not None


def test_journal_roundtrip_and_corruption(tmp_path):
    arrays = {"conv": np.arange(24, dtype=np.float32).reshape(2, 3, 4),
              "ssm": np.ones((2, 8), np.float32)}
    path = journal_path(str(tmp_path), b"\x01" * 16)
    write_journal(path, arrays, 16)
    out = read_journal(path)
    assert out is not None
    np.testing.assert_array_equal(out["conv"], arrays["conv"])
    np.testing.assert_array_equal(out["ssm"], arrays["ssm"])
    # Bit-flip the payload: the CRC must catch it.
    data = bytearray(open(path, "rb").read())
    data[len(data) // 2] ^= 0xFF
    open(path, "wb").write(bytes(data))
    assert read_journal(path) is None
    # Injected corruption on a GOOD file (the deterministic drill).
    write_journal(path, arrays, 16)
    fi.inject("ssm.restore_corrupt", max_fires=1)
    try:
        assert read_journal(path) is None
        assert read_journal(path) is not None  # single fire
    finally:
        fi.clear("ssm.restore_corrupt")


def test_journal_gc_reclaims_orphans_keeps_live(tmp_path, monkeypatch):
    """Retention sweep (PR8 known gap: content-addressed journal files
    were never deleted): TTL-expired orphans and over-budget old files
    are reclaimed on manager init and on reset()/sleep; fresh and
    still-referenced checkpoints survive both passes."""
    import time

    from vllm_distributed_tpu.core.state_cache import (journal_path,
                                                       sweep_journal,
                                                       write_journal)
    jd = str(tmp_path / "journal")
    os.makedirs(jd)
    arrays = {"conv": np.arange(8, dtype=np.float32)}

    def make_file(tag, age_s=0.0, size=0):
        path = journal_path(jd, tag.encode())
        write_journal(path, arrays, num_tokens=8)
        if size:
            with open(path, "ab") as f:  # inflate for budget tests
                f.write(b"\0" * size)
        if age_s:
            old = time.time() - age_s
            os.utime(path, (old, old))
        return path

    # TTL pass: a week-old orphan dies at init, fresh files survive.
    expired = make_file("expired", age_s=8 * 86400)
    fresh = make_file("fresh")
    monkeypatch.setenv("VDT_SSM_CKPT_TTL_S", "604800")
    monkeypatch.setenv("VDT_SSM_CKPT_MAX_MB", "1024")
    m = _mgr(journal_dir=jd)
    assert not os.path.exists(expired)
    assert os.path.exists(fresh)
    assert m.journal_files_reclaimed == 1
    assert m.stats()["ssm_journal_reclaimed"] == 1

    # Budget pass at sleep: oldest-first eviction down to the budget —
    # but a checkpoint a pending persist still OWES is never reclaimed,
    # whatever its age.
    owed = make_file("owed", age_s=3600, size=1 << 20)
    bulk = [make_file(f"bulk{i}", age_s=1800 - i, size=1 << 20)
            for i in range(3)]

    class _Persist:
        journal = owed

    m.pending_persists.append(_Persist())
    monkeypatch.setenv("VDT_SSM_CKPT_MAX_MB", "2")
    m.reset()
    assert os.path.exists(owed)  # referenced: survives over-budget
    survivors = [p for p in bulk if os.path.exists(p)]
    total = sum(os.path.getsize(p) for p in (owed, fresh, *survivors))
    assert len(survivors) < len(bulk)  # oldest bulk files reclaimed
    # Unreferenced files were evicted oldest-first until the
    # unprotected remainder fit the budget.
    assert sum(os.path.getsize(p) for p in survivors) <= 2 << 20
    assert m.journal_files_reclaimed > 1

    # Direct sweep unit: keep-set beats both TTL and budget.
    kept = make_file("kept", age_s=30 * 86400)
    removed, _ = sweep_journal(jd, max_bytes=1, ttl_s=60,
                               keep={kept, owed, fresh})
    assert os.path.exists(kept) and os.path.exists(owed)
    assert removed >= len(survivors)


def test_dp_merge_sums_ssm_counters():
    """The vdt:ssm_* families merge across DP replicas through the
    aggregator's numeric-sum loop — flat keys, no special cases."""
    from vllm_distributed_tpu.engine.dp_client import DPEngineClient
    client = DPEngineClient.__new__(DPEngineClient)
    client.clients = [None, None]
    client._down = set()
    client.replica_failovers = 0
    client.replica_resurrections = 0
    client.request_counts = lambda: [0, 0]
    per = [
        {"ssm_state_cache_hits": 3, "ssm_state_cache_queries": 5,
         "ssm_state_cache_evictions": 1, "ssm_checkpoints": 7,
         "ssm_state_bytes_held": 100, "ssm_resume_tokens_saved": 64},
        {"ssm_state_cache_hits": 2, "ssm_state_cache_queries": 4,
         "ssm_state_cache_evictions": 0, "ssm_checkpoints": 3,
         "ssm_state_bytes_held": 50, "ssm_resume_tokens_saved": 16},
    ]
    agg = client._aggregate_stats(per)
    assert agg["ssm_state_cache_hits"] == 5
    assert agg["ssm_state_cache_queries"] == 9
    assert agg["ssm_state_cache_evictions"] == 1
    assert agg["ssm_checkpoints"] == 10
    assert agg["ssm_state_bytes_held"] == 150
    assert agg["ssm_resume_tokens_saved"] == 80
    # And they render on /metrics with their registered names.
    from vllm_distributed_tpu.metrics.prometheus import render_metrics
    text = render_metrics(agg)
    assert "vdt:ssm_state_cache_hits_total 5.0" in text
    assert "vdt:ssm_checkpoints_total 10.0" in text
    assert "vdt:ssm_state_bytes_held 150.0" in text


# ---------------------------------------------------------------------------
# State rows beside latent pages (Ling-3.0's two kinds of cache for one
# request; ``models/bailing.py``, ``core/state_cache.py``) through the
# scheduler, the KV manager and the runner at a toy width: a preempted
# request parks its row and resumes to the logits of an uninterrupted
# run; a snapshot taken at a chunk boundary restores the state, and only
# while the latent pages of its prefix are resident.
# ---------------------------------------------------------------------------
LING_DATA = os.path.join(os.path.dirname(__file__), "..", "benchmarking",
                         "data")


@pytest.fixture(scope="module")
def ling_dir(tmp_path_factory):
    from benchmarks import run
    cell = run.load_cell("tiny-ling.tiny-decode", LING_DATA)
    work = run.WORK
    run.WORK = str(tmp_path_factory.mktemp("kda-rows"))
    try:
        return run.write_model_dir(cell["config"], cell["config_file"])
    finally:
        run.WORK = work


def _make_ling_engine(model_dir, monkeypatch, cache_on=True, **over):
    return _make_engine(model_dir, monkeypatch, cache_on=cache_on,
                        interval=16, load_format="dummy", seed=5,
                        block_size=16, max_model_len=128, max_num_seqs=4,
                        **over)


@pytest.fixture(scope="module")
def ling_engine(ling_dir):
    """Ling's engine with ``_make_ling_engine``'s own arguments (a pool
    of 128 pages, the state cache on), for the tests below in turn: they
    serve prompts that share no page, and read counters as differences."""
    with pytest.MonkeyPatch.context() as mp:
        return _make_ling_engine(ling_dir, mp)


def _drain_logprobs(engine, max_steps=600):
    """{request: (tokens, the chosen tokens' logprobs)}."""
    done = {}
    for _ in range(max_steps):
        for out in engine.step():
            if out.finished:
                c = out.outputs[0]
                done[out.request_id] = (
                    list(c.token_ids),
                    [d[t] for t, d in zip(c.token_ids, c.logprobs)])
        if not engine.has_unfinished_requests():
            break
    return done


def _close(a, b, tol=2e-4):
    return len(a) == len(b) and all(abs(x - y) < tol for x, y in zip(a, b))


def test_preempted_request_parks_its_row_and_resumes(ling_dir, ling_engine,
                                                     monkeypatch):
    """Four requests of 24 + 40 tokens against a pool of 15 pages of 16
    (a request needs 4, so the last to want its fourth page is
    preempted while the others hold theirs to the end): its row is
    parked in the snapshot pool, the pages of its prefix stay in the
    prefix cache, and it resumes as a continuation. Tokens and logprobs
    equal those of a run that was never interrupted (a pool of 128
    pages). The engine of its own differs by
    ``num_gpu_blocks_override=15``."""
    sp = SamplingParams(temperature=0.0, max_tokens=40, ignore_eos=True,
                        logprobs=1)
    prompts = [[(i * 37 + j * 11 + 3) % 500 for j in range(24)]
               for i in range(4)]

    def go(engine):
        before = int(engine.get_stats()["num_preemptions"])
        for i, p in enumerate(prompts):
            engine.add_request(f"r-{i}", p, sp)
        done = _drain_logprobs(engine)
        stats = engine.get_stats()
        free = engine.engine_core.engine_core.scheduler \
            .kv_cache_manager.get_num_free_blocks()
        return (done, int(stats["num_preemptions"]) - before,
                _ssm_stats(engine), free)

    calm, none, _, _ = go(ling_engine)
    tight, preempted, stats, free = go(_make_ling_engine(
        ling_dir, monkeypatch, num_gpu_blocks_override=15))
    assert none == 0 and preempted > 0
    assert free == 15                       # nothing leaked
    for rid, (tokens, lps) in calm.items():
        assert tight[rid][0] == tokens, rid
        assert _close(tight[rid][1], lps), rid
    assert stats["ssm_state_cache_hits"] >= 1, stats
    assert stats["ssm_resume_tokens_saved"] > 0, stats


def test_chunk_boundary_snapshot_needs_the_prefix_pages(ling_dir,
                                                        ling_engine,
                                                        monkeypatch):
    """A second turn whose prompt extends the first restores the state
    row from the snapshot at the last chunk boundary AND reuses the
    latent pages of that prefix. With the pages dropped from the prefix
    cache the snapshot alone is no resume point: no hit, and the whole
    prompt is prefilled again (the same second turn once more on the
    same engine: the snapshots are all still there, the pages are not).
    The logits are the same every way. The engine with the state cache
    off is its own."""
    sp = SamplingParams(temperature=0.0, max_tokens=6, ignore_eos=True,
                        logprobs=1)
    first = [(j * 7 + 3) % 500 for j in range(70)]

    def second_turn(engine, tag, drop_pages=False):
        before = _ssm_stats(engine)
        sched = engine.engine_core.engine_core.scheduler
        if drop_pages:
            assert sched.kv_cache_manager.reset_prefix_cache()
        engine.add_request(f"{tag}-1", first + tokens + [17, 18, 19], sp)
        return (_drain_logprobs(engine)[f"{tag}-1"], before,
                _ssm_stats(engine), sched)

    with monkeypatch.context() as off:    # the runner reads it later too
        engine = _make_ling_engine(ling_dir, off, cache_on=False)
        engine.add_request("plain-0", list(first), sp)
        tokens, _ = _drain_logprobs(engine)["plain-0"]
        plain, _, _, _ = second_turn(engine, "plain")
    ling_engine.add_request("hit-0", list(first), sp)
    assert _drain_logprobs(ling_engine)["hit-0"][0] == tokens
    hit, before, after, sched = second_turn(ling_engine, "hit")
    assert sched.kv_cache_manager.enable_caching     # forced on: hybrid
    assert sched.state_cache.paged_kv
    assert after["ssm_state_cache_hits"] == \
        before.get("ssm_state_cache_hits", 0) + 1, (before, after)
    # 64 of the 79 prompt tokens were not computed again.
    assert after["ssm_resume_tokens_saved"] >= \
        before.get("ssm_resume_tokens_saved", 0) + 64
    miss, before, after, _ = second_turn(ling_engine, "miss",
                                         drop_pages=True)
    assert after["ssm_state_cache_hits"] == \
        before.get("ssm_state_cache_hits", 0), (before, after)
    for got in (hit, miss):
        assert got[0] == plain[0]
        assert _close(got[1], plain[1])
