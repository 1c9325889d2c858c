"""The reduction by the program's own marks: name stacks read from the
protobuf, device time by scope and by kernel, the two clocks aligned by
causal pairs, and the readers of the metrics built on them -- on synthetic
traces counted by hand."""
import pytest

from perfbench import cell, spans, spec
from perfbench import trace as tr

D = 1000.0                      # host time = device time + D, in ns


def _dev(a, b):
    """A device interval given in host time."""
    return a - D, b - D


def _trace(early=0):
    # Window 0..10000 host ns, two epochs, one replan in the second.
    #   epoch 1: dispatch.epoch 100-200; jit_epoch runs at host 300-2000;
    #            sync.trigger 200-2000 (the read returns as the program ends)
    #   epoch 2: dispatch.epoch 5000-5100; jit_epoch runs at host 5000-6000
    #            (the tightest start); sync.trigger 5100-6500;
    #            dispatch.replan 6600-6700; jit_replan runs at 6800-9000;
    #            sync.plan_word 6700-9300
    # ``early`` begins the second dispatch.epoch that much sooner, which
    # lowers the offset's lower bound to D - early.
    ops = tr.line([
        ("%fusion.1 = f32[8] fusion(x)", *_dev(300, 2000)),
        ("%while.2 = (f32[8]) while(y)", *_dev(5000, 6000)),
        ("%fusion.3 = f32[8] fusion(y)", *_dev(5000, 5600)),
        ("%jvp_noma_intra_up_fwd_.4 = f32[8] custom-call(z)",
         *_dev(5600, 6000)),
        ("%while.5 = (f32[8]) while(w)", *_dev(6800, 8800)),
        ("%noma_intra_up_fwd.6 = f32[8] custom-call(w)", *_dev(6800, 7800)),
        ("%transpose_jvp_noma_contract_up_bwd__.7 = f32[8] custom-call(w)",
         *_dev(7800, 8800)),
        ("%fusion.8 = f32[8] fusion(v)", *_dev(8800, 9000)),
    ])
    modules = tr.line([("jit_epoch(1)", *_dev(300, 2000)),
                       ("jit_epoch(1)", *_dev(5000, 6000)),
                       ("jit_replan(2)", *_dev(6800, 9000))])
    host = tr.line([
        (tr.WINDOW, 0, 10000),
        ("perfbench.epoch", 50, 2100), ("perfbench.epoch", 4900, 9400),
        ("dispatch.epoch", 100, 200), ("sync.trigger", 200, 2000),
        ("dispatch.epoch", 5000 - early, 5100), ("sync.trigger", 5100, 6500),
        ("dispatch.replan", 6600, 6700), ("perfbench.replan", 6610, 6690),
        ("sync.plan_word", 6700, 9300),
    ])
    return tr.Trace(window=(0.0, 10000.0), ops=[ops], modules=[modules],
                    host=host)


TF_OPS = {
    "%while.2 = (f32[8]) while(y)": "jit(replan)/while/body/gd_iter/while",
    "%fusion.3 = f32[8] fusion(y)":
        "jit(replan)/while/body/gd_iter/while/body/jvp()/mul",
    "%jvp_noma_intra_up_fwd_.4 = f32[8] custom-call(z)":
        "jit(replan)/while/body/gd_iter/while/body/jvp(noma_intra_up_fwd)/"
        "pallas_call",
    "%noma_intra_up_fwd.6 = f32[8] custom-call(w)":
        "jit(replan)/while/body/warm_gate/noma_intra_up_fwd/pallas_call",
    "%fusion.8 = f32[8] fusion(v)": "jit(replan)/greedy_rounding/while/add",
}


def test_the_scope_is_a_path_component():
    assert spans.in_scope("jit(replan)/while/body/gd_iter/while", "gd_iter")
    assert spans.in_scope("a/transpose(jvp(warm_gate))/b", "warm_gate")
    assert spans.in_scope("jit(f)/gd_iter", "gd_iter")
    assert not spans.in_scope("jit(f)/gd_iter2/mul", "gd_iter")
    assert not spans.in_scope("jit(f)/my_gd_iter/mul", "gd_iter")
    assert not spans.in_scope("", "gd_iter")


def test_scope_seconds_count_leaves_under_the_scope():
    t = _trace()
    # gd_iter: fusion.3 (600) + the kernel .4 (400); while.2 is a parent
    assert spans.scope_seconds(t, TF_OPS, "gd_iter") == pytest.approx(1e-6)
    assert spans.scope_seconds(t, TF_OPS, "warm_gate") == \
        pytest.approx(1000e-9)
    assert spans.scope_seconds(t, TF_OPS, "greedy_rounding") == \
        pytest.approx(200e-9)
    assert spans.scope_seconds(t, {}, "gd_iter") == 0.0


def test_kernels_by_the_name_their_scope_gives_them():
    assert spans.kernel_seconds(_trace()) == {
        "noma_contract_up_bwd": (1, pytest.approx(1000e-9)),
        "noma_intra_up_fwd": (2, pytest.approx(1400e-9)),
    }


def test_the_clock_offset_is_recovered_from_causal_pairs():
    off = spans.clock_offset(_trace())
    # lower bounds: 100-(300-D), 5000-(5000-D), 6600-(6800-D) -> D
    # upper bounds: 2000-(2000-D), 6500-(6000-D) -> D
    assert (off.lo, off.hi, off.mid) == (D, D, D)
    assert off.pairs == 3 + 2


def test_unpaired_spans_give_no_offset():
    t = _trace()
    cpu = t._replace(ops=[], modules=[])            # no device plane
    assert spans.clock_offset(cpu) is None
    parent = t._replace(host=tr.line([(tr.WINDOW, 0, 10000)]))
    assert spans.clock_offset(parent) is None       # no program spans


def test_idle_inside_sync_spans_on_the_aligned_clock():
    t = _trace()
    # chip busy (host clock): 300-2000, 5000-6000, 6800-9000.
    # sync.trigger 200-2000: idle 200-300 = 100
    # sync.trigger 5100-6500: idle 6000-6500 = 500
    # sync.plan_word 6700-9300: idle 6700-6800 and 9000-9300 = 400
    assert spans.sync_idle_s(t, D) == pytest.approx(1000e-9)
    # read on the device clock unshifted, the same spans cover other gaps
    assert spans.sync_idle_s(t, 0.0) != pytest.approx(1000e-9)


def test_idle_split_by_span():
    t = _trace()
    split = spans.idle_by_span(t, D)
    # idle: 0-300, 2000-5000, 6000-6800, 9000-10000 (5100 ns in all)
    assert sum(split.values()) == pytest.approx(5100e-9)
    assert split["sync"] == pytest.approx(1000e-9)
    # dispatch.epoch 100-200 and dispatch.replan 6600-6700
    assert split["dispatch"] == pytest.approx(200e-9)
    # perfbench.epoch spans outside the program's: 50-100, 2000-2100,
    # 4900-5000, 6500-6600 and 9300-9400
    assert split["harness"] == pytest.approx(450e-9)
    # 0-50, 2100-4900, 9400-10000
    assert split["none"] == pytest.approx(3450e-9)


def test_report_splits_idle_at_each_end_of_the_offset_interval():
    rep = spans.report(_trace(early=100), TF_OPS)
    off = rep["clock_offset_ns"]
    assert (off["lo"], off["hi"], off["pairs"]) == (D - 100, D, 5)
    # device shifted 100 ns earlier (lo): sync idle 1900-2000, 5900-6500,
    # 8900-9300; 50 ns earlier (mid): 200-250, 1950-2000, 5950-6500,
    # 6700-6750, 8950-9300; at D (hi) as in the test above
    assert [rep["idle_s"][end]["sync"] for end in ("lo", "mid", "hi")] == \
        pytest.approx([1100e-9, 1050e-9, 1000e-9])
    assert rep["jit_replan"] == (1, pytest.approx(2200e-9))
    assert rep["scopes_s"]["gd_iter"] == pytest.approx(1e-6)
    assert rep["host_reads"] == 3


def test_host_reads_begun_in_the_window():
    assert spans.sync_count(_trace()) == 3


def _field(number, wire, payload):
    key = bytes([(number << 3) | wire])
    if wire == 0:
        return key + _varint(payload)
    return key + _varint(len(payload)) + payload


def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _entry(key, value):
    return _field(1, 0, key) + _field(2, 2, value)


def test_name_stacks_are_read_from_the_wire_format(tmp_path):
    """One device plane with a str-valued and a ref-valued tf_op, and a
    host plane that is left out."""
    stat_meta = (_field(5, 2, _entry(7, _field(1, 0, 7)
                                         + _field(2, 2, b"tf_op")))
                 + _field(5, 2, _entry(9, _field(1, 0, 9)
                                       + _field(2, 2, b"jit(f)/gd_iter/mul"))))
    op1 = (_field(1, 0, 1) + _field(2, 2, b"%fusion.1 = f32[8] fusion(x)")
           + _field(5, 2, _field(1, 0, 7) + _field(5, 2, b"jit(f)/a/add")))
    op2 = (_field(1, 0, 2) + _field(2, 2, b"%mul.2 = f32[8] multiply(x)")
           + _field(5, 2, _field(1, 0, 7) + _field(7, 0, 9)))
    device = (_field(1, 0, 3) + _field(2, 2, b"/device:TPU:0")
              + _field(3, 2, b"\x08\x01")                 # a line, skipped
              + _field(4, 2, _entry(1, op1)) + _field(4, 2, _entry(2, op2))
              + stat_meta)
    host = (_field(2, 2, b"/host:CPU") + stat_meta
            + _field(4, 2, _entry(1, op1)))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, 2, device) + _field(1, 2, host)
                     + _field(4, 2, b"somehost"))
    assert spans.op_scopes(str(path)) == {
        "%fusion.1 = f32[8] fusion(x)": "jit(f)/a/add",
        "%mul.2 = f32[8] multiply(x)": "jit(f)/gd_iter/mul",
    }


def test_name_stacks_of_a_recorded_tpu_trace():
    from pathlib import Path
    path = Path(__file__).resolve().parent / "data" / "tpu_pallas_grad.xplane.pb"
    stacks = spans.op_scopes(str(path))
    calls = [s for s in stacks.values() if s.endswith("/pallas_call:")]
    assert len(stacks) == 28 and len(calls) == 8
    assert all(s.startswith("jit(<lambda>)/") for s in stacks.values())


def _run(t, epochs=2, replans=1):
    c = spec.cell("vgg16-einsum.replan10")
    return cell.RunData(cell=c, setup_s=1.0, window_s=1e-5,
                        epoch_s=[5e-6] * epochs, epochs=epochs,
                        replans=replans, gd_iters=49, sizes=None, peak=None,
                        trace=t)


@pytest.mark.parametrize("metric, want", [
    ("replan_program_ms", 2200e-9 * 1e3),       # one 2200 ns replan
    ("host_syncs_per_epoch", 1.5),               # 2 triggers + 1 plan word
    ("sync_idle_ms", 1000e-9 / 2 * 1e3),
])
def test_readers_on_the_synthetic_trace(metric, want):
    read = spec.reader(spec.ROOT, metric)
    assert read(_run(_trace())) == pytest.approx(want)


@pytest.mark.parametrize("metric", ["replan_program_ms",
                                    "host_syncs_per_epoch", "sync_idle_ms"])
def test_readers_find_nothing_without_the_program_marks(metric):
    """The parent's programs lower as jit_wrapped and open no spans: the
    readers return nothing, and nothing raises."""
    t = _trace()
    old = t._replace(
        modules=[tr.line([("jit_epoch(1)", *_dev(300, 2000)),
                          ("jit_wrapped(2)", *_dev(6800, 9000))])],
        host=tr.line([(tr.WINDOW, 0, 10000), ("perfbench.epoch", 50, 2100)]))
    read = spec.reader(spec.ROOT, metric)
    assert read(_run(old)) is None
    assert read(_run(None)) is None


# -- what the chip emits ------------------------------------------------------
# A traced window of each cell cut to rehearse.TINY_DEPLOYMENT on one v5e
# (perfbench/record.py --tiny, 10 einsum epochs with one replan, 2 Pallas
# epochs with a replan each), with its /host:metadata plane (HLO protos)
# dropped to keep the files small.
CHIP = {"einsum": "tpu_loop_einsum.xplane.pb",
        "pallas": "tpu_loop_pallas.xplane.pb"}
REPLANS = {"einsum": 1, "pallas": 2}


@pytest.fixture(scope="module", params=sorted(CHIP))
def chip(request):
    from pathlib import Path
    path = str(Path(__file__).resolve().parent / "data" / CHIP[request.param])
    return request.param, tr.load(path), spans.op_scopes(path)


def test_the_chip_runs_the_programs_by_name(chip):
    cell, t, _ = chip
    n, _ = tr.module_seconds(t._replace(window=(0.0, 1e15)), "jit_replan")
    modules = {tr.base_name(name) for name in t.modules[0].names}
    assert {"jit_epoch", "jit_replan"} <= modules
    assert "jit_wrapped" not in modules and n == REPLANS[cell]


@pytest.mark.parametrize("scope", spans.SCOPES)
def test_the_chip_carries_each_phase_scope(chip, scope):
    _, t, stacks = chip
    assert any(s.startswith("jit(replan)/") and spans.in_scope(s, scope)
               for s in stacks.values())
    assert spans.scope_seconds(t, stacks, scope) > 0


def test_the_chip_names_each_kernel_call(chip):
    """Per replan: 175 forward calls of each forward kernel (49 iterations
    of a gradient and a step's Gamma, 25 start Gammas, 50 warm-gate probes,
    2 discrete utilities of the "best" rule) and 49 of each backward one."""
    cell, t, _ = chip
    kernels = spans.kernel_seconds(t)
    if cell == "einsum":
        assert kernels == {}
        return
    r = REPLANS[cell]
    assert {k: n for k, (n, _) in kernels.items()} == {
        "noma_intra_up_fwd": 175 * r, "noma_intra_dn_fwd": 175 * r,
        "noma_per_ap_up_fwd": 175 * r, "noma_contract_dn_fwd": 175 * r,
        "noma_intra_up_bwd": 49 * r, "noma_intra_dn_bwd": 49 * r,
        "noma_contract_up_bwd": 49 * r, "noma_per_ap_dn_bwd": 49 * r}


def test_the_chip_trace_holds_the_program_spans(chip):
    import collections
    cell, t, _ = chip
    names = collections.Counter(t.host.names[i] for i in t.host.name)
    epochs = names["perfbench.epoch"]
    assert names["dispatch.epoch"] == names["sync.trigger"] == epochs
    assert names["dispatch.replan"] == names["sync.plan_word"] \
        == REPLANS[cell]
    assert spans.sync_count(t) == epochs + REPLANS[cell]
    off = spans.clock_offset(t)
    assert off is not None and off.lo <= off.hi
