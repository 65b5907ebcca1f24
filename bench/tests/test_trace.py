"""The trace reduction on a small synthetic trace."""
import pytest

from benchlib import trace
from benchlib.trace import Trace

MS = 1e6   # ns


def sample():
    ops = [("fusion.1", 0 * MS, 2 * MS), ("paged_attention.9", 1 * MS, 2 * MS),
           ("fusion.1", 5 * MS, 1 * MS), ("paged_prefill_attention.2", 9 * MS, 1 * MS)]
    modules = [("jit_decode_step(1)", 0, 3 * MS),
               ("jit_decode_step(1)", 5 * MS, 1 * MS)]
    host = [("bench.step", 0, 7 * MS), ("bench.wait", 7 * MS, 1.8 * MS),
            ("bench.step", 8.8 * MS, 1.7 * MS)]
    return Trace(ops, modules, host)


def test_busy_union_and_window():
    t = sample()
    assert trace.union(t.ops) == [(0, 3 * MS), (5 * MS, 6 * MS),
                                  (9 * MS, 10 * MS)]
    assert trace.busy_ns(t) == pytest.approx(5 * MS)
    assert trace.window(t) == (0, 10.5 * MS)


def test_busy_share_inside_step_spans():
    t = sample()
    steps = trace.host_spans(t, "step")
    # steps cover 8.7 ms, of which the device is busy 3 + 1 + 1 ms
    assert trace.busy_share_of(t, steps) == pytest.approx(5 / 8.7)


def test_idle_gaps_named_by_host_span():
    gaps = trace.idle_gaps(sample())
    assert [g[0] for g in gaps] == ["wait", "step", "step"]
    assert [g[1] for g in gaps] == pytest.approx([3e-3, 2e-3, 0.5e-3])


def test_loop_ops_give_way_to_their_body():
    t = Trace([("while.5", 0, 10 * MS), ("fusion.2", 1 * MS, 3 * MS),
               ("fusion.3", 5 * MS, 4 * MS), ("copy.1", 12 * MS, 1 * MS)],
              [], [])
    assert [e[0] for e in trace.leaves(t.ops)] == ["fusion.2", "fusion.3",
                                                  "copy.1"]
    assert trace.top_ops(t, 1) == [["fusion.3", pytest.approx(4e-3)]]
    assert trace.busy_ns(t) == pytest.approx(11 * MS)


def test_top_ops_and_matching():
    t = sample()
    top = trace.top_ops(t, 2)
    assert top[0] == ["fusion.1", pytest.approx(3e-3)]
    assert len(top) == 2
    assert trace.matching(t.ops, ("paged_attention.9",)) == [t.ops[1]]


def program_sample():
    """`sample()` with the program's spans and device scopes: two steps,
    each with a decode and the second with a chunk, and one idle gap in
    `serving.sample` inside `bench.step` and `serving.step`. The ops do
    not overlap, so their sum is the busy time."""
    t = sample()
    t.ops[1] = ("paged_attention.9", 2 * MS, 1 * MS)
    t.spans = [("serving.step", 0, 6.9 * MS, {"step": 4}),
               ("serving.decode", 0.5 * MS, 2 * MS, {"rows": 3, "keys": 90}),
               ("serving.sample", 3 * MS, 2.5 * MS, {}),
               ("serving.step", 8.85 * MS, 1.6 * MS, {"step": 5}),
               ("serving.chunk", 8.9 * MS, 1.2 * MS,
                {"start": 32, "len": 32, "plans": 2}),
               ("serving.decode", 10.1 * MS, 0.3 * MS, {"rows": 1, "keys": 7})]
    paths = {"fusion.1": "jit(decode_step)/jit(main)/while/body/serve_matmul/"
                         "dot_general",
             "paged_attention.9": "jit(decode_step)/jit(main)/while/body/"
                                  "attention/jit(paged_attention)/"
                                  "pallas_call"}
    t.op_scope = trace.scopes_of(
        t.ops, t.modules, {"jit_decode_step(1)": paths}, trace.SCOPES)
    return t


def test_program_spans_keep_their_arguments():
    t = program_sample()
    assert [s[3] for s in trace.program_spans(t, "step")] == [{"step": 4},
                                                             {"step": 5}]
    chunk, = trace.program_spans(t, "chunk")
    assert chunk[3] == {"start": 32, "len": 32, "plans": 2}
    assert [s[3]["rows"] for s in trace.program_spans(t, "decode")] == [3, 1]


@pytest.mark.parametrize("path,scopes,want", [
    ("jit(f)/while/body/attention/jit(paged_attention)/serve_matmul/dot",
     trace.SCOPES, "serve_matmul"),
    ("jit(f)/while/body/attention/jit(paged_attention)/pallas_call",
     trace.SCOPES, "attention"),
    ("jit(f)/while/body/add", trace.SCOPES, None),
    ("jit(f)/experts/serve_matmul/router/dot", trace.SCOPES | {"router"},
     "router"),
    ("jit(f)/my_attention/dot", trace.SCOPES, None),
])
def test_innermost_scope_on_the_op_path(path, scopes, want):
    assert trace.innermost(path, scopes) == want


def test_op_scope_from_the_program_the_op_ran_in():
    t = program_sample()
    assert t.op_scope == {
        ("fusion.1", 0): "serve_matmul", ("paged_attention.9", 2 * MS):
        "attention", ("fusion.1", 5 * MS): "serve_matmul",
        # outside any program event: no path, no scope
        ("paged_prefill_attention.2", 9 * MS): None}


def test_idle_gaps_named_by_innermost_span():
    gaps = trace.idle_gaps(program_sample())
    # 6-9 ms: 1.05 ms in serving.step, 0.15 in bench.step alone, 1.8 in
    # bench.wait; 3-5 ms in bench.step > serving.step > serving.sample;
    # 10-10.5 ms: 0.3 ms of it in serving.decode
    assert [g[0] for g in gaps] == ["wait", "serving.sample",
                                    "serving.decode"]
    assert [g[1] for g in gaps] == pytest.approx([3e-3, 2e-3, 0.5e-3])


def test_device_ms_by_scope_sums_to_busy_time():
    t = program_sample()
    by_step = trace.step_scopes(t)
    assert by_step["steps"] == 2
    assert by_step["ms"] == pytest.approx(
        {"serve_matmul": 1.5, "attention": 0.5, None: 0.5})
    assert sum(by_step["ms"].values()) == pytest.approx(by_step["busy_ms"])


def test_op_names_read_from_the_programs_hlo_in_a_real_trace(tmp_path):
    """The HLO the profiler stores in the trace (its "/host:metadata"
    plane) gives each instruction's op-name path, scopes and all; read
    here from a CPU trace of a jitted function with two scopes, one of
    them inside a loop."""
    import glob

    import jax
    import jax.numpy as jnp

    def body(i, c):
        with jax.named_scope("serve_matmul"):
            return jnp.tanh(c @ c)

    @jax.jit
    def scoped(x):
        with jax.named_scope("attention"):
            y = jax.nn.softmax(x * 3.0)
        return jax.lax.fori_loop(0, 3, body, y)

    x = jnp.ones((64, 64))
    scoped(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    scoped(x).block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    programs = trace.hlo_op_names(path)
    name, = [n for n in programs if n.startswith("jit_scoped(")]
    assert trace.hlo_op_names(path, {name}) == {name: programs[name]}
    scopes = {trace.innermost(p, trace.SCOPES)
              for p in programs[name].values()}
    assert {"attention", "serve_matmul"} <= scopes
