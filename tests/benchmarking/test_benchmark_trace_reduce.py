"""Trace arithmetic on a hand-built event list, and the loader on a trace
recorded here from a CPU jit."""

import pytest

from benchmarks import layers, trace_reduce as tr

# One device line: a layer loop (while) holding a kernel and a fusion,
# then a lone fusion; 10 s span, busy 0-4 and 6-7.
OPS = [("while.2", 0.0, 4.0), ("unified_write_attend_pallas.6", 0.5, 2.0),
       ("fusion.12", 2.5, 1.0), ("fusion.40", 6.0, 1.0)]
HOST = [("vdt_step", 3.5, 2.0), ("$model_runner.py:1078 _prepare_inputs",
                                 4.2, 0.6), ("vdt_step", 9.5, 0.4)]


def test_busy_idle_union_and_shares():
    assert tr.merge([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tr.busy_seconds(OPS) == pytest.approx(5.0)
    assert tr.busy_seconds(OPS, 1.0, 6.5) == pytest.approx(3.5)
    assert tr.idle_share(OPS, 0.0, 10.0) == pytest.approx(50.0)
    assert tr.span(OPS) == (0.0, 7.0)
    assert tr.share_of_busy(OPS, "attend") == pytest.approx(40.0)
    assert tr.share_of_busy(OPS, "ragged") == pytest.approx(0.0)
    assert tr.share_of_busy([], "x") is None
    assert tr.median_ms(OPS, r"^fusion") == pytest.approx(1000.0)
    assert tr.median_ms(OPS, "nothing") is None
    assert tr.ms_per_step(OPS, r"^fusion", 4) == pytest.approx(500.0)
    assert tr.ms_per_step(OPS, r"^fusion", 0) is None


def test_self_time_and_top_ops_do_not_count_a_loop_twice():
    self_t = {n: d for n, _, d in tr.self_times(OPS)}
    assert self_t["while.2"] == pytest.approx(1.0)
    assert tr.top_ops(OPS, 2) == [["unified_write_attend_pallas", 2.0],
                                  ["fusion", 2.0]]
    assert sum(v for _, v in tr.top_ops(OPS, 10)) == pytest.approx(5.0)


def test_idle_gaps_are_named_by_what_the_host_was_in():
    gaps = dict(tr.idle_gaps(OPS, HOST, 0.0, 10.0))
    # 4-6: its middle (5.0) lies in the first vdt_step only; 7-10: its
    # middle (8.5) lies in no host span.
    assert gaps == {"between_steps": pytest.approx(3.0),
                    "in:vdt_step": pytest.approx(2.0)}
    inner = dict(tr.idle_gaps([("a", 0.0, 4.2), ("b", 4.6, 1.0)], HOST,
                              0.0, 5.6))
    assert list(inner) == ["in:$model_runner.py:1078 _prepare_inputs"]


def test_a_gap_is_named_by_the_engine_core_thread_s_innermost_span():
    """Two host threads: the API thread's short `vdt.process_outputs`
    covers the gap's middle too, and is not what the chip waits for."""
    engine = [("vdt_step", 3.5, 2.0), ("vdt.dispatch_model", 3.6, 1.8),
              ("vdt.launch", 4.4, 0.9), ("vdt.loop", 7.0, 2.5),
              ("vdt_step", 9.5, 0.4), ("PjitFunction(add)", 4.9, 0.2)]
    api = [("vdt.process_outputs", 4.8, 0.4),
           ("vdt.process_outputs", 8.4, 0.2)]
    ctx = layers.Context([], [], [], {}, "/nonexistent")
    ctx._planes = {"/device:TPU:0": {"XLA Ops": OPS},
                   "/host:CPU": {"python3": api, "python3#2": engine}}
    # Gap 4-6: middle 5.0, in `vdt.launch` (JAX's own event there is no
    # span of the program's and names nothing); the span ends at 7.0.
    assert dict(ctx.breakdown()["idle_gaps"]) == {
        "in:vdt.launch": pytest.approx(2.0)}
    assert ctx.steps() == 2
    gaps = dict(tr.idle_gaps(OPS, ctx.engine_thread(), 0.0, 10.0))
    assert gaps == {"in:vdt.launch": pytest.approx(2.0),
                    "in:vdt.loop": pytest.approx(3.0)}
    for name in ("vdt.launch", "vdt.device_wait", "vdt.finish", "vdt.loop",
                 "vdt._update_states", "vdt._prepare_inputs",
                 "vdt.process_outputs", "vdt_step"):
        assert tr.matching([(name, 0, 1)], layers.HOST_SPANS), name


def test_plane_selection_and_event_names():
    planes = {"/device:TPU:0": {"XLA Ops": OPS}, "/device:TPU:1": {},
              "/host:CPU": {"python3": HOST}, "#Chip0 Misc": {},
              "/device:CUSTOM:Megascale Trace": {}}
    assert tr.device_planes(planes) == ["/device:TPU:0", "/device:TPU:1"]
    assert tr.short_name(
        "%fusion.12 = (bf16[72,2048]{1,0}) fusion(bf16[8] %p), kind=kLoop"
    ) == "fusion.12"
    assert tr.short_name("jit_forward(123)") == "jit_forward(123)"


def test_context_reduces_a_trace_through_the_metric_files(tmp_path):
    ctx = layers.Context([], [], [], {}, str(tmp_path))
    ctx._planes = {"/device:TPU:0": {
        "XLA Ops": OPS, "XLA Modules": [("jit_forward(1)", 0.0, 4.0),
                                        ("jit_forward(1)", 6.0, 1.0),
                                        ("jit_sample(2)", 7.0, 0.001)]},
        "/host:CPU": {"python3": HOST}}
    wanted = [{"name": n} for n in ("device.idle_share", "kernel.attn_share",
                                    "model.step_device_ms_p50",
                                    "kernel.moe_gemm_share")]
    got = layers.evaluate_all(wanted, ctx)
    assert got["device.idle_share"] == pytest.approx(100 * 2 / 7)
    assert got["kernel.attn_share"] == pytest.approx(40.0)
    assert got["model.step_device_ms_p50"] == pytest.approx(2500.0)
    assert got["kernel.moe_gemm_share"] == pytest.approx(0.0)
    assert ctx.device_times() == {"busy_s": pytest.approx(5.0),
                                  "window_s": pytest.approx(7.0)}
    b = ctx.breakdown()
    assert b["device_ops"][0][0] == "unified_write_attend_pallas"
    assert dict(b["idle_gaps"]) == {"in:vdt_step": pytest.approx(2.0)}
    assert ctx.steps() == 2


def test_load_reads_a_trace_recorded_from_a_cpu_jit(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    import threading

    def api_thread():
        with jax.profiler.TraceAnnotation("vdt.process_outputs"):
            f(x).block_until_ready()

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.StepTraceAnnotation("vdt_step", step_num=0):
        f(x).block_until_ready()
    other = threading.Thread(target=api_thread)
    other.start()
    other.join(60)
    jax.profiler.stop_trace()
    path = tr.find_trace(str(tmp_path))
    assert path and path.endswith(".xplane.pb")
    planes = tr.load(path)
    assert tr.HOST_PLANE in planes
    steps = [e for line in planes[tr.HOST_PLANE].values()
             for e in tr.matching(line, r"^vdt_step")]
    assert steps and all(d > 0 for _, _, d in steps)
    # Two threads of one process: two lines of one name, kept apart.
    mine = [name for name, line in planes[tr.HOST_PLANE].items()
            if tr.matching(line, r"^vdt")]
    assert len(mine) == 2 and len({n.partition("#")[0] for n in mine}) == 1
    ctx = layers.Context([], [], [], {}, str(tmp_path))
    assert [e[0] for e in ctx.engine_thread()] == ["vdt_step"]
    # The CPU has no device plane: every trace reader then reads nothing.
    assert tr.device_planes(planes) == []
    ctx = layers.Context([], [], [], {}, str(tmp_path))
    assert ctx.window() is None and ctx.breakdown() is None
    assert ctx.device_times() == {}
