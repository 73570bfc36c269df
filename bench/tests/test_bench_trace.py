"""The trace reduction on a small recorded trace: busy and idle share,
per-program and per-op device time, collective time, and idle gaps named
by the host span open in them."""
import pytest

from bench.harness import trace as tr
from bench.harness.spans import WINDOW_SPAN, Spans


def _events():
    ms = 1e6
    ev = tr.TraceEvents()
    for dev in ("/device:TPU:0", "/device:TPU:1"):
        ev.ops[dev] = [("fusion.1", 0 * ms, 3 * ms),
                       ("all-reduce.7", 3 * ms, 1 * ms),
                       ("_kernel", 6 * ms, 2 * ms)]
        ev.modules[dev] = [("jit__train_impl(11)", 0 * ms, 4 * ms),
                           ("jit_sig(12)", 6 * ms, 2 * ms)]
    ev.host = [(WINDOW_SPAN, 0.0, 10 * ms),
               ("flush_cohort", 0.0, 9 * ms),
               ("front_half", 4 * ms, 2 * ms),
               ("publish", 8 * ms, 2 * ms)]
    return ev


def test_busy_idle_and_programs():
    r = tr.reduce_events(_events())
    assert r["window_s"] == pytest.approx(10e-3)
    assert r["busy_s"] == pytest.approx(6e-3)
    assert r["devices"] == 2
    assert r["modules"]["jit__train_impl"] == pytest.approx(4e-3)
    assert r["ops"]["_kernel"] == pytest.approx(2e-3)
    assert r["collective_s"] == pytest.approx(1e-3)
    assert tr.seconds_matching(r["modules"], r"_train(_uniform)?_impl") == \
        pytest.approx(4e-3)


def test_kernel_time_by_program():
    """A Pallas op is known by its stats, not its name, and its time goes
    to the program whose event holds it."""
    ms = 1e6
    assert tr.is_kernel("closed_call.10", {
        "tf_op": "jit(serve_prefill)/while/body/closed_call/pallas_call"})
    assert tr.is_kernel("_sig_impl.3", {"long_name": "custom-call(%x), "
                                        "custom_call_target=\"tpu_custom_call\""})
    assert not tr.is_kernel("fusion.1", {"tf_op": "jit(f)/dot_general"})
    # a TPU v5e trace names the op by its whole HLO instruction
    assert tr.is_kernel(
        '%closed_call.10 = bf16[16,16,512,128]{3,2,1,0} custom-call(bf16[16,'
        '16,512,128]{3,2,1,0} %fusion.111), custom_call_target='
        '"tpu_custom_call", frontend_attributes={kernel_metadata={}}', {})
    assert not tr.is_kernel(
        "%fusion.899 = f32[2,4608,512]{2,1,0} fusion(bf16[2,512,4608]{2,1,0} "
        "%custom-call.97), kind=kOutput", {})
    ev = _events()
    for dev in ev.modules:
        ev.kernels[dev] = [("closed_call.10", 6.5 * ms, 1 * ms),
                           ("closed_call.10", 1 * ms, 0.5 * ms)]
    r = tr.reduce_events(ev)
    assert r["kernels"] == {"jit_sig": pytest.approx(1e-3),
                            "jit__train_impl": pytest.approx(0.5e-3)}
    assert tr.seconds_matching(r["kernels"], r"^jit_sig$") == \
        pytest.approx(1e-3)


def test_idle_gaps_named_by_innermost_span():
    r = tr.reduce_events(_events())
    gaps = dict(r["breakdown"]["idle_gaps"])
    # 4..6 ms: inside flush_cohort and front_half -> the innermost one
    assert gaps["front_half"] == pytest.approx(2e-3)
    # 8..10 ms: publish (9..10 ms lies outside flush_cohort)
    assert gaps["publish"] == pytest.approx(2e-3)
    ops = [name for name, _ in r["breakdown"]["device_ops"]]
    assert ops[0] == "fusion.1"


def test_clip_to_window_and_union():
    ev = tr.TraceEvents(
        ops={"d": [("a", 0, 10), ("b", 5, 10), ("c", 30, 10)]},
        host=[(WINDOW_SPAN, 5, 30)])
    r = tr.reduce_events(ev)
    assert r["busy_s"] == pytest.approx(15e-9)
    assert r["window_s"] == pytest.approx(30e-9)


def test_host_spans_read_from_a_recorded_cpu_trace(tmp_path):
    jax = pytest.importorskip("jax")
    import glob
    spans = Spans(annotate=True)
    f = jax.jit(lambda x: (x @ x).sum())
    x = jax.numpy.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with spans.span(WINDOW_SPAN):
        with spans.span("flush_cohort"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    ev = tr.read_xplane(path, {WINDOW_SPAN, "flush_cohort"})
    names = {n for n, _, _ in ev.host}
    assert names == {WINDOW_SPAN, "flush_cohort"}
    lo, hi = tr.window_of(ev)
    assert hi > lo
