"""The reduction from a trace to busy time, module and kernel time and
idle gaps: on a synthetic trace counted by hand, and on a trace recorded
here on the CPU."""
import jax
import jax.numpy as jnp
import pytest

from perfbench import trace as tr


def _synthetic():
    # Window 100..200 ns. Chip 0 ops: 90-110 (clipped to 100-110), 110-120,
    # a while loop 140-170 and 190-230 (clipped to 190-200).
    # The while loop 140-170 holds fusion.1 at 150-160.
    ops = tr.line([("%fusion.1 = f32[8] fusion(x)", 90, 110),
                   ("%custom-call.3 = f32[8] custom-call(x), "
                    'custom_call_target="tpu_custom_call"', 110, 120),
                   ("%while.2 = (f32[8]) while(y)", 140, 170),
                   ("%fusion.1 = f32[8] fusion(x)", 150, 160),
                   ("%copy.2 = f32[8] copy(z)", 190, 230)])
    modules = tr.line([("jit_epoch(12)", 100, 120), ("jit_wrapped(7)", 140, 170),
                       ("jit_wrapped(7)", 190, 200), ("jit_other(3)", 10, 20)])
    host = tr.line([(tr.WINDOW, 100, 200), ("perfbench.epoch", 100, 200),
                    ("PjitFunction(wrapped)", 122, 138)])
    return tr.Trace(window=(100, 200), ops=[ops], modules=[modules], host=host)


def test_union_merges_overlaps():
    a, b = tr.union([5, 1, 2, 7], [7, 3, 4, 8])
    assert a.tolist() == [1, 5] and b.tolist() == [4, 8]
    a, b = tr.union([1, 2], [10, 3])            # one holds the other
    assert a.tolist() == [1] and b.tolist() == [10]


def test_busy_is_the_union_inside_the_window():
    t = _synthetic()
    assert t.window_s == pytest.approx(100e-9)
    # 100-120, 140-170, 190-200
    assert tr.busy_s(t) == pytest.approx(60e-9)


def test_module_seconds_by_base_name():
    t = _synthetic()
    assert tr.module_seconds(t, "jit_epoch") == (1, pytest.approx(20e-9))
    assert tr.module_seconds(t, "jit_wrapped") == (2, pytest.approx(40e-9))
    assert tr.module_seconds(t, "jit_other") == (0, 0.0)   # outside


def test_op_seconds_match_the_hlo_text():
    t = _synthetic()
    assert tr.op_seconds(t, "tpu_custom_call") == (1, pytest.approx(10e-9))
    assert tr.op_seconds(t, r"^%fusion") == (2, pytest.approx(20e-9))


def test_top_ops_and_idle_gaps():
    t = _synthetic()
    top = dict(tr.top_ops(t))
    # the while loop holds fusion.1, so only the leaves count
    assert "%while.2" not in top
    assert top["%fusion.1"] == pytest.approx(20e-9)
    assert top["%custom-call.3"] == pytest.approx(10e-9)
    gaps = tr.idle_gaps(t)
    # gaps: 120-140 (host in PjitFunction), 170-190 (only the epoch span)
    assert [g[1] for g in gaps] == [pytest.approx(20e-9)] * 2
    assert [g[0] for g in gaps] == ["host: PjitFunction(wrapped)",
                                    "host: perfbench.epoch"]


def test_largest_idle_gap_of_each_epoch():
    t = _synthetic()
    # one epoch span: the two 20 ns gaps are both in it
    assert tr.epoch_largest_gaps(t, "perfbench.epoch").tolist() == \
        [pytest.approx(20e-9)]
    # three epochs 100-130, 130-160, 160-200: gap 120-140 (middle 130)
    # falls in the second, gap 170-190 in the third, none in the first
    t = t._replace(host=tr.line([(tr.WINDOW, 100, 200), ("e", 160, 200),
                                 ("e", 100, 130), ("e", 130, 160)]))
    assert tr.epoch_largest_gaps(t, "e").tolist() == \
        [0.0, pytest.approx(20e-9), pytest.approx(20e-9)]
    assert tr.epoch_largest_gaps(t, "none").size == 0


def test_two_chips_average():
    one = _synthetic()
    t = one._replace(ops=one.ops * 2, modules=one.modules * 2)
    assert tr.busy_s(t) == pytest.approx(60e-9)
    assert tr.module_seconds(t, "jit_wrapped") == (2, pytest.approx(40e-9))


def test_load_a_recorded_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(tr.find_xplane(str(tmp_path)))
    assert t.window[1] > t.window[0]
    assert tr.WINDOW in t.host.names
    assert t.ops == [] and tr.busy_s(t) == 0.0     # no TPU plane on the CPU
    assert tr.idle_gaps(t) == []


def test_a_recorded_tpu_trace_of_the_noma_kernels():
    """A v5e trace of one value-and-gradient of Gamma_5 on the Pallas path
    at U=64, N=4, M=128: both links forward and backward, so four cell-intra
    calls, two per-AP and two AP-contraction calls."""
    from pathlib import Path

    from perfbench import work
    t = tr.load(str(Path(__file__).resolve().parent / "data"
                    / "tpu_pallas_grad.xplane.pb"))
    assert t.n_chips == 1 and t.ops[0].start.size > 0
    # On this short trace the device clock runs behind the host's window
    # mark, so widen the window to the whole trace.
    t = t._replace(window=(0.0, 1e15))
    z = work.sizes(64, 4, 128, [0] * 64)
    counts = {k: tr.op_seconds(t, p)[0]
              for k, p in work.noma_kernels(z).items()}
    assert counts == {"intra": 4, "per_ap": 2, "contract": 2}
    assert tr.busy_s(t) > 0
    assert tr.module_seconds(t, "jit_wrapped")[0] == 0
