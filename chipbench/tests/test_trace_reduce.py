"""The trace reduction on a trace recorded on a TPU v5e: 6.1 s of the
``r1q15-reason-closed32`` window with the benchmark's host spans, the
decode step's ``paged_attention_decode_none`` kernel and its other
device ops."""
import lzma
import shutil

import pytest

from chipbench import trace_reduce
from chipbench.tests.faults import HERE

TRACE = HERE / "data" / "reason-decode.xplane.pb.xz"


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    d = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    with lzma.open(TRACE) as src, open(d / "t.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    path = trace_reduce.find_xplane(str(d.parents[2]))
    return trace_reduce.reduce(path)


def test_device_busy_time(summary):
    assert summary.chips == 1
    assert summary.busy_s == pytest.approx(5.850290507, rel=1e-9)
    span_s = (summary.last_ns - summary.first_ns) * 1e-9
    assert summary.busy_s <= span_s <= 6.2


def test_kernel_time_by_name(summary):
    kernel = summary.op_seconds(r"paged_attention_decode")
    assert kernel == pytest.approx(5.689616719, rel=1e-9)
    assert summary.op_s["paged_attention_decode_none"] == kernel
    # op names lose XLA's uniquifier and the instruction text
    assert all(" " not in n and not n.endswith(tuple("0123456789"))
               for n in summary.op_s)
    # ops may overlap, so their sum is at least the busy time
    assert sum(summary.op_s.values()) >= summary.busy_s


def test_idle_gaps_are_labelled_by_host_spans(summary):
    labels = dict(summary.gaps)
    assert set(labels) <= {"scheduler", "decode", "admit_full",
                           "admit_prefix", "prefill_chunk", "between steps"}
    assert labels["scheduler"] > labels["decode"] > 0
    idle = sum(labels.values())
    span_s = (summary.last_ns - summary.first_ns) * 1e-9
    assert idle == pytest.approx(span_s - summary.busy_s, rel=1e-6)
    assert [s for _, s in summary.gaps] == sorted(labels.values(),
                                                  reverse=True)
