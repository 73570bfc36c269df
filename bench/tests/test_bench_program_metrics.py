"""The per-layer reader of a device name the program gives its kernels: on
a small recorded trace reduced as a chip trace is, and with nothing to
read."""
from types import SimpleNamespace

import pytest

from bench.harness import loader
from bench.harness import trace as tr
from bench.harness.spans import WINDOW_SPAN


def _read(name, **r):
    base = dict(trace=None, raw={})
    base.update(r)
    return loader.metric_reader(name).read(SimpleNamespace(**base))


def _trace(*extra):
    """A signature kernel as a v5e trace names it: the whole HLO
    instruction, named after the kernel, and an op that reads its result."""
    ms = 1e6
    ev = tr.TraceEvents()
    dev = "/device:TPU:0"
    sig = ('%dagafl_signature.7 = f32[4,1,512]{2,1,0} custom-call(f32[4,128,'
           '512]{2,1,0} %fusion.3), custom_call_target="tpu_custom_call"')
    other = ('%closed_call.2 = bf16[2,4,128]{2,1,0} custom-call(bf16[2,4,'
             '128]{2,1,0} %p), custom_call_target="tpu_custom_call"')
    consumer = ('%fusion.9 = f32[4,512]{1,0} fusion(f32[4,1,512]{2,1,0} '
                '%dagafl_signature.7), kind=kLoop')
    ev.ops[dev] = [("fusion.1", 0, 3 * ms), (sig, 3 * ms, 0.25 * ms),
                   (consumer, 3.25 * ms, 0.5 * ms), (other, 4 * ms, 1 * ms),
                   (sig, 6 * ms, 0.25 * ms)]
    ev.ops[dev] += [(n, 7 * ms + i * ms, 0.5 * ms)
                    for i, n in enumerate(extra)]
    ev.host = [(WINDOW_SPAN, 0.0, 10 * ms)]
    return tr.reduce_events(ev)


def test_signature_kernel_reader_on_a_recorded_trace():
    # 0.5 ms of kernel over 2 cohort windows; the op reading its result
    # is not the kernel
    assert _read("signature_kernel_ms.train", trace=_trace(),
                 raw={"flushes": 2}) == pytest.approx(0.25)


def test_signature_kernel_reader_counts_only_its_kernel():
    rows = ('%dagafl_signature_rows.2 = f32[8,1,512]{2,1,0} custom-call('
            'f32[8,128,512]{2,1,0} %p.1), custom_call_target="tpu_custom_call"')
    t = _trace(rows)
    assert _read("signature_kernel_ms.train", trace=t,
                 raw={"flushes": 2}) == pytest.approx(0.25)
    t["ops"] = {k: v for k, v in t["ops"].items()
                if not k.startswith("%dagafl_signature.")}
    assert _read("signature_kernel_ms.train", trace=t,
                 raw={"flushes": 2}) is None


def test_signature_kernel_reader_finds_nothing_without_a_trace_or_name():
    name = "signature_kernel_ms.train"
    assert _read(name, raw={"flushes": 2}) is None
    assert _read(name, trace=_trace()) is None
    ev = tr.TraceEvents(ops={"/device:TPU:0": [("fusion.1", 0, 10)]},
                        op_stats={"fusion.1": {}})
    assert _read(name, trace=tr.reduce_events(ev), raw={"flushes": 2}) is None
