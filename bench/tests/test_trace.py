"""The trace reduction, on a small trace written out by hand.

The trace (``data/small_trace.pbtxt``): a window span of 1000..11000 ns on
the host; on the chip, operations at 500..2000 and 1500..3000 (overlapping,
the first partly before the window), 5000..6000, 8000..9000 and
10500..12000 (partly after it), a loop (``while``) at 1000..9500 that only
holds other operations, and one module run at 1000..9500.  Operations are
named by their HLO instruction text, as on a TPU.  The
host spans bench.step 2500..7000, bench.dispatch 7000..7500 and bench.wait
9200..10000 (and one bench.step after the window).
"""
import base64
from pathlib import Path

import pytest

from bench.trace import kernel_ops, summarize

DATA = Path(__file__).parent / "data" / "small_trace.pbtxt"


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData
    return summarize(ProfileData.from_text_proto(DATA.read_text()))


def test_window_and_busy_union(summary):
    assert summary.window_s == pytest.approx(10000e-9)
    # union inside the window: 1000..3000, 5000..6000, 8000..9000, 10500..11000
    assert summary.busy_s == pytest.approx(4500e-9)
    assert 1 - summary.busy_s / summary.window_s == pytest.approx(0.55)
    assert summary.n_chips == 1


def test_containers_are_not_operations(summary):
    assert "while.51" not in summary.op_s
    assert summary.op_kind["closed_call.13"] == "custom-call"


def test_per_operation_and_module_time(summary):
    assert summary.op_s["closed_call.13"] == pytest.approx(2000e-9)
    assert summary.op_s["body.9"] == pytest.approx(1500e-9)
    assert summary.op_s["fusion.1"] == pytest.approx(1500e-9)
    assert summary.module_s["jit__mixed_step_fn(1)"] == pytest.approx(8500e-9)


def test_idle_gaps_by_host_span(summary):
    # gaps 3000..5000, 6000..8000, 9000..10500
    idle = summary.idle_by_span
    assert idle["bench.step"] == pytest.approx(3000e-9)
    assert idle["bench.dispatch"] == pytest.approx(500e-9)
    assert idle["bench.wait"] == pytest.approx(800e-9)
    assert idle["(no bench span)"] == pytest.approx(1200e-9)
    assert sum(idle.values()) == pytest.approx(summary.window_s - summary.busy_s)


def test_kernel_ops_found_by_kernel_function_name():
    def line(instr, fn):
        body = base64.b64encode(b"MLIR..." + fn.encode() + b"...").decode()
        return (f'  %{instr} = bf16[8]{{0}} custom-call(%a), '
                f'custom_call_target="tpu_custom_call", '
                f'backend_config={{"custom_call_config":{{"body":"{body}"}}}}')
    hlo = "\n".join([line("closed_call.13", "_paged_mixed_kernel"),
                     line("body.9", "_lmhead_epilogue_kernel"),
                     "  %fusion.1 = bf16[8]{0} fusion(%a), kind=kLoop"])
    found = kernel_ops(hlo, {"attn": "_paged_mixed_kernel",
                             "head": "_lmhead_epilogue_kernel"})
    assert found == {"closed_call.13": "attn", "body.9": "head"}
