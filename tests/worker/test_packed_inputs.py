"""The packed hand-over (worker/step_inputs.py, sample/metadata.py)
against the per-array one it replaced.

``reference_inputs`` builds, with one plain loop over the scheduled
requests, the arrays that the per-array hand-over shipped one
``jnp.asarray`` each (its vectorised pure-decode path gave the same
arrays as its loop). Every step of every case must unpack, element for
element and with the same dtypes (floats bit for bit), to those arrays;
the served greedy tokens must be HF's."""

import copy

import jax
import numpy as np
import pytest

from tests.engine.test_llm_engine import (checkpoint, hf_greedy,  # noqa: F401
                                          make_engine)
from vllm_distributed_tpu.metrics.prometheus import render_metrics
from vllm_distributed_tpu.ops.pallas_attention import (
    Q_TILE_PAD, build_partition_descriptor, num_partition_programs)
from vllm_distributed_tpu.sample.metadata import (pack_sampling,
                                                  unpack_sampling)
from vllm_distributed_tpu.sampling_params import SamplingParams
from vllm_distributed_tpu.utils import pad_to_bucket
from vllm_distributed_tpu.worker.step_inputs import StepLayout

FLOATS = ("temperature", "top_p", "min_p")


def _runner(engine):
    return engine.engine_core.executor.worker.model_runner


def reference_inputs(runner, so, rng) -> tuple[dict, int, int]:
    ib, ps = runner.input_batch, runner.page_size
    num_sched = so.num_scheduled_tokens
    T, max_q, G = runner._batch_shape(so.total_num_scheduled_tokens,
                                      max(num_sched.values()))
    a = {"token_ids": np.zeros((T, ), np.int32),
         "req_idx": np.zeros((T, ), np.int32),
         "positions": np.zeros((T, ), np.int32),
         "slot_mapping": np.full((T, ), -1, np.int32),
         "block_tables": ib.block_table.copy(),
         "seq_lens": ib.num_computed.copy(),
         "seq_info": np.zeros((runner.max_num_reqs, 4), np.int32),
         "kv_runs": np.zeros((G, 4), np.int32)}
    t = runs = n_kv = 0
    sampling_rows, logits_idx = [], []
    for req_id, n in num_sched.items():
        row = ib.req_id_to_index[req_id]
        start = int(ib.num_computed[row])
        end = start + n
        pos = np.arange(start, end)
        a["token_ids"][t:t + n] = ib.token_ids[row, start:end]
        a["positions"][t:t + n] = pos
        a["req_idx"][t:t + n] = row
        a["slot_mapping"][t:t + n] = (
            ib.block_table[row, pos // ps] * ps + pos % ps)
        a["seq_info"][runs] = (t, n, end, row)
        runs += 1
        done = 0
        while done < n:  # maximal spans of consecutive slots in one page
            off = (start + done) % ps
            length = min(ps - off, n - done)
            a["kv_runs"][n_kv] = (
                ib.block_table[row, (start + done) // ps], off,
                t + done - off + ps, length)
            n_kv += 1
            done += length
        if end >= ib.num_tokens[row]:
            sampling_rows.append(row)
            logits_idx.append(t + n - 1)
        t += n
    a["num_seqs"] = np.asarray([runs], np.int32)
    a["num_kv_runs"] = np.asarray([n_kv], np.int32)
    bq, sb = runner._tile_params()
    a["attn_desc"], a["decode_list"] = build_partition_descriptor(
        a["seq_info"], runs, bq=bq, sb=sb,
        num_programs=num_partition_programs(
            T - Q_TILE_PAD, runner.max_num_reqs, bq=bq, sb=sb,
            num_kv_writes=G),
        num_kv_writes=n_kv)
    R = pad_to_bucket(max(len(sampling_rows), 1), runner.req_buckets)
    pad = [0] * (R - len(sampling_rows))
    rows = np.asarray(sampling_rows + pad, np.int32)
    a["logits_indices"] = np.asarray(logits_idx + pad, np.int32)
    for name in ("temperature", "top_k", "top_p", "min_p"):
        a[name] = getattr(ib, name)[rows]
    seeds = np.where(ib.seed[rows] >= 0,
                     ib.seed[rows] * 1000003
                     + ib.num_tokens[rows].astype(np.int64),
                     rng.integers(0, 2**31 - 1, size=R))
    # What jnp.asarray made of the int64 seeds on their way to the device.
    a["seeds"] = np.asarray(jax.numpy.asarray(seeds))
    a["statics"] = (max_q, bq, sb)
    return a, len(sampling_rows), R


@pytest.fixture(scope="module")
def spied(checkpoint):
    """An engine whose every _prepare_inputs is recorded beside the
    reference's arrays for the same SchedulerOutput."""
    path, hf = checkpoint
    engine = make_engine(path, max_num_batched_tokens=16, max_num_seqs=4)
    runner = _runner(engine)
    steps: list[dict] = []
    orig = runner._prepare_inputs

    def spy(so):
        ib = runner.input_batch
        facts = {"pure_decode": runner._fast_decode_rows(so) is not None,
                 "reqs": []}
        for req_id, n in so.num_scheduled_tokens.items():
            row = ib.req_id_to_index[req_id]
            start = int(ib.num_computed[row])
            facts["reqs"].append((start, n, int(ib.prompt_len[row])))
        want, facts["sampled"], facts["R"] = reference_inputs(
            runner, so, copy.deepcopy(runner._rng))
        out = orig(so)
        inputs, logits_indices, sampling_packed = out[:3]
        token_ids, batch = inputs.unpack()
        md = unpack_sampling(sampling_packed)
        got = {"token_ids": token_ids, "logits_indices": logits_indices}
        for name in want:
            if name in got or name == "statics":
                continue
            got[name] = getattr(md if hasattr(md, name) else batch, name)
        got["statics"] = (batch.max_q, batch.attn_bq, batch.attn_sb)
        steps.append({"want": want, "got": got, "facts": facts})
        return out

    runner._prepare_inputs = spy
    return engine, hf, steps


def _hist(engine):
    return engine.get_stats()["step_host_transfers"]


def _serve(engine, arrivals, max_steps=200) -> dict:
    """arrivals: [(step at which to add it, request id, prompt, params)]"""
    done = {}
    for i in range(max_steps):
        for at, rid, prompt, sp in arrivals:
            if at == i:
                engine.add_request(rid, prompt, sp)
        for out in engine.step():
            if out.finished:
                done[out.request_id] = out.outputs[0].token_ids
        if i >= max(a[0] for a in arrivals) and \
                not engine.has_unfinished_requests():
            break
    assert not engine.has_unfinished_requests()
    return done


def _greedy(n):
    return SamplingParams(temperature=0.0, max_tokens=n, ignore_eos=True)


def _prompts(seed, sizes):
    rng = np.random.default_rng(seed)
    return [[int(x) for x in rng.integers(2, 127, size=n)] for n in sizes]


def _crosses_page(start, n, ps=4):
    return start // ps != (start + n - 1) // ps


# case -> (prompt sizes with their arrival steps and output lengths,
# predicates that some step of the case must satisfy, each)
CASES = {
    "pure_decode": (
        [(0, 3, 6), (0, 5, 6)],
        [lambda f: f["pure_decode"]]),
    "mixed_prefill_and_decode": (
        [(0, 3, 12), (0, 2, 12), (3, 13, 4)],
        [lambda f: any(n > 1 for _, n, _ in f["reqs"])
         and any(s >= p for s, _, p in f["reqs"])]),
    "chunked_prefill_continues": (
        [(0, 40, 4)],
        [lambda f: any(0 < s and s + n < p for s, n, p in f["reqs"])]),
    "crosses_a_page_boundary": (
        [(0, 6, 8), (0, 3, 8)],
        # a prefill over two pages; a decode token that opens a page
        [lambda f: any(_crosses_page(s, n) for s, n, _ in f["reqs"]),
         lambda f: any(n == 1 and s % 4 == 0 for s, n, _ in f["reqs"])]),
    "fills_a_request_bucket": (
        [(0, 3, 5), (0, 4, 5), (0, 5, 5), (0, 2, 5)],
        [lambda f: f["pure_decode"] and f["sampled"] == f["R"] == 4]),
}


@pytest.mark.parametrize("case", list(CASES))
def test_unpacked_inputs_equal_the_per_array_hand_over(spied, case):
    engine, hf, steps = spied
    traffic, must_occur = CASES[case]
    del steps[:]
    prompts = _prompts(len(case), [size for _, size, _ in traffic])
    arrivals = [(at, f"{case}-{i}", prompts[i], _greedy(n_out))
                for i, (at, _, n_out) in enumerate(traffic)]
    # One request asks for seeded sampling too, so the seeds' fold and
    # wrap and a nonzero temperature are compared as well.
    arrivals.append((0, f"{case}-seeded", [5, 9, 11],
                     SamplingParams(temperature=0.7, top_k=5, top_p=0.9,
                                    min_p=0.05, seed=2**33 + 12345,
                                    max_tokens=3, ignore_eos=True)))
    if case == "fills_a_request_bucket":
        arrivals.pop()  # the bucket of 4 is to be filled exactly
    before = _hist(engine)
    done = _serve(engine, arrivals)
    after = _hist(engine)
    # The default path: the packed forward buffer from _prepare_inputs;
    # the sampled rows' indices and the packed sampling buffer from the
    # launch, behind the forward.
    assert after["count"] - before["count"] == len(steps)
    assert after["sum"] - before["sum"] == 3 * len(steps)
    for occurs in must_occur:
        assert any(occurs(s["facts"]) for s in steps)
    for s in steps:
        want, got = s["want"], s["got"]
        assert set(want) == set(got)
        assert want.pop("statics") == got.pop("statics")
        for name in want:
            w, g = want[name], np.asarray(got[name])
            assert g.dtype == w.dtype, name
            assert g.shape == w.shape, name
            if name in FLOATS:
                w, g = w.view(np.int32), g.view(np.int32)
            assert np.array_equal(w, g), (case, name)
    for i, (_, _, n_out) in enumerate(traffic):
        assert done[f"{case}-{i}"] == hf_greedy(hf, prompts[i], n_out)


def test_layout_is_a_function_of_the_shape_key():
    a = StepLayout(T=48, G=24, max_reqs=4, pages_per_req=16,
                   desc_programs=30)
    assert a == StepLayout(48, 24, 4, 16, 30) and hash(a) == hash(
        StepLayout(48, 24, 4, 16, 30))
    names = [name for name, *_ in a.fields]
    assert names[0] == "token_ids"  # the async chain indexes it flat
    assert a.size == 4 * 48 + 4 * 16 + 4 + 16 + 1 + 24 * 4 + 1 + 90 + 4
    legacy = StepLayout(48, 24, 4, 16, 0)  # no descriptor (MLA models)
    assert "attn_desc" not in [n for n, *_ in legacy.fields]
    buf = a.new_buffer()
    views = a.views(buf)
    assert (views["slot_mapping"] == -1).all() and buf.sum() == -48
    views["kv_runs"][3] = (7, 1, 2, 3)   # a view writes the buffer
    off = {n: start for n, start, _, _ in a.fields}["kv_runs"]
    assert list(buf[off + 12:off + 16]) == [7, 1, 2, 3]
    assert a.new_buffer().sum() == -48   # and not the template


def test_sampling_floats_ride_bit_exact():
    t = np.asarray([0.0, 0.7, 1e-30, np.float32(1) / 3], np.float32)
    seeds = np.asarray([0, 2**31 + 5, -1, 2**40 + 7], np.int64)
    packed = pack_sampling(t, np.arange(4, dtype=np.int32), t[::-1], t,
                           seeds)
    assert packed.shape == (5, 4) and packed.dtype == np.int32
    md = unpack_sampling(jax.numpy.asarray(packed))
    assert np.asarray(md.temperature).tobytes() == t.tobytes()
    assert np.asarray(md.top_p).tobytes() == t[::-1].tobytes()
    assert list(np.asarray(md.seeds)) == [0, -2**31 + 5, -1, 7]


def test_default_steps_count_three_transfers_and_optional_fields_more(
        checkpoint):
    path, _ = checkpoint
    engine = make_engine(path, max_num_batched_tokens=16, max_num_seqs=4)
    prompts = _prompts(3, (3, 9, 21))
    _serve(engine, [(0, f"d{i}", p, _greedy(5))
                    for i, p in enumerate(prompts)])
    h = _hist(engine)
    steps = h["count"]
    assert steps > 5 and h["sum"] == 3 * steps
    assert h["counts"][h["buckets"].index(3)] == steps
    text = render_metrics(engine.get_stats())
    assert f"vdt:step_host_transfers_count {steps}\n" in text
    assert f'vdt:step_host_transfers_bucket{{le="3"}} {steps}\n' in text
    assert 'vdt:step_host_transfers_bucket{le="2"} 0\n' in text
    # A step that scores prompt logprobs ships the scored rows and their
    # targets beside the three: it still runs, and counts more.
    done = _serve(engine, [(0, "plp", prompts[1], SamplingParams(
        temperature=0.0, max_tokens=2, ignore_eos=True,
        prompt_logprobs=2))])
    assert len(done["plp"]) == 2
    h2 = _hist(engine)
    more = h2["count"] - steps
    assert more >= 2 and h2["sum"] - h["sum"] > 3 * more
    # So does a step with logit processors (the extended sampler's
    # fields are transfers of their own).
    done = _serve(engine, [(0, "pen", prompts[0], SamplingParams(
        temperature=0.0, max_tokens=3, ignore_eos=True,
        repetition_penalty=1.3))])
    assert len(done["pen"]) == 3
    h3 = _hist(engine)
    assert h3["sum"] - h2["sum"] > 3 * (h3["count"] - h2["count"])
