"""The trace reduction: busy union, idle gaps and what the host was doing in them."""
import time

import pytest

import fedbench_tiny as ft
import trace_reduce as tr


def test_merge_busy_and_gaps_of_known_intervals():
    ops = [(0, 10), (5, 20), (30, 40), (38, 45), (60, 70)]
    assert tr.merge(ops, 0, 100) == [(0, 20), (30, 45), (60, 70)]
    assert tr.busy_ns(ops, 0, 100) == 45
    assert tr.busy_ns(ops, 8, 35) == 17  # clipped to the window
    assert tr.idle_gaps(ops, 0, 100) == [(20, 30), (45, 60), (70, 100)]
    assert tr.idle_gaps([], 0, 5) == [(0, 5)]


def test_innermost_span_names_the_shortest_open_span():
    spans = [("window", 0, 100), ("round", 10, 50), ("draw", 12, 20)]
    assert tr.innermost_span(spans, 15) == "draw"
    assert tr.innermost_span(spans, 30) == "round"
    assert tr.innermost_span(spans, 70) == "none"


def test_op_names_lose_their_text():
    assert tr.op_name("%fusion.84 = bf16[500,784]{1,0} fusion(...)") == "fusion.84"
    assert tr.op_name("jit_batched_round_step(2559558977365123103)") == "jit_batched_round_step"


def test_reduction_of_a_trace_recorded_on_cpu(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax import profiler

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = profiler.ProfileOptions()
    opts.python_tracer_level = 0
    profiler.start_trace(str(tmp_path), profiler_options=opts)
    with profiler.TraceAnnotation("window"):
        for _ in range(3):
            with profiler.TraceAnnotation("round"):
                with profiler.TraceAnnotation("draw"):
                    time.sleep(0.03)  # the host works, the device waits
                with profiler.TraceAnnotation("local_work"):
                    with profiler.TraceAnnotation("fl.local_work", slots=4, share=0.5, tag="x"):
                        f(x).block_until_ready()
    profiler.stop_trace()
    trace = tr.collect(profiler.ProfileData.from_file(tr.find_xplane(str(tmp_path))),
                       **ft.CPU_TRACE)
    assert sorted({n for n, _, _ in trace.spans}) == ["draw", "local_work", "round", "window"]
    # the program's spans are kept apart, with their numeric arguments only
    assert [(n, args) for n, _, _, args in trace.program] == [
        ("fl.local_work", {"slots": 4, "share": 0.5})] * 3
    assert [len(v) for v in tr.program_spans(trace).values()] == [3]
    s = tr.device_summary(trace)
    assert 0.09 <= s["window_s"] < 5.0
    assert 0.0 < s["busy_s"] < s["window_s"]
    assert 1.0 - s["busy_s"] / s["window_s"] > 0.5  # three 30 ms sleeps
    label, seconds = s["idle_gaps"][0]
    assert label == "draw" and seconds == pytest.approx(0.03, abs=0.015)
    spans = tr.span_seconds(trace)
    assert len(spans["round"]) == 3 and len(spans["draw"]) == 3
    assert all(d >= 0.03 for d in spans["draw"])
