"""The host-span reduction, on a small trace written out by hand.

The trace (``data/program_spans.pbtxt``): a window span of 1000..21000 ns;
on the chip, operations at 500..1500 (partly before the window),
4500..9800 and 14200..17900.  On the host thread, bench.dispatch 1000..2000
holding serve.dispatch 1200..1800, then two bench.step spans, 2000..12000
and 12000..20000, each holding a serve.step (2100..11900, rows 3;
12100..19900, rows 4) that holds, in order, serve.fill, serve.pack,
serve.launch, serve.sync (4000..10000; 14000..17500), serve.fold and
serve.rollback.  A third bench.step (20500..23000) and its serve.step
(20600..22900) run past the window's end.
"""
from pathlib import Path

import pytest

from bench import run, spans
from bench.trace import summarize as trace_summary

DATA = Path(__file__).parent / "data"


def _profile(name):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto((DATA / name).read_text())


@pytest.fixture(scope="module")
def prog():
    return spans.summarize(_profile("program_spans.pbtxt"))


def test_serve_spans_nest_inside_bench_steps(prog):
    steps = [s for s in prog.spans if s.name == "serve.step"]
    assert [s.args for s in steps] == [{"rows": 3, "k": 8}, {"rows": 4, "k": 8},
                                       {"rows": 4, "k": 8}]
    outer = [s for s in prog.spans if s.name == "bench.step"]
    for st in steps:
        assert any(b.start <= st.start and st.end <= b.end for b in outer)
    inner = [s for s in prog.spans
             if s.name.startswith("serve.") and s.name not in
             ("serve.step", "serve.dispatch") and s.end <= 20000]
    names = [s.name for s in inner]
    assert names == 2 * ["serve.fill", "serve.pack", "serve.launch",
                         "serve.sync", "serve.fold", "serve.rollback"]
    for s in inner:
        assert any(st.start <= s.start and s.end <= st.end for st in steps)
    d = [s for s in prog.spans if s.name == "serve.dispatch"]
    assert [(x.start, x.end) for x in d] == [(1200, 1800)]


def test_idle_goes_to_the_innermost_span(prog):
    # gaps 1500..4500, 9800..14200, 17900..21000
    ns = {k: round(v * 1e9) for k, v in prog.idle_by_span.items()}
    assert ns == {"serve.dispatch": 300, "bench.dispatch": 200,
                  "bench.step": 500, "serve.step": 700, "serve.fill": 800,
                  "serve.pack": 1600, "serve.launch": 1300,
                  "serve.sync": 900, "serve.fold": 2100,
                  "serve.rollback": 1600, spans.NO_SPAN: 500}


def test_idle_seconds_add_up_to_the_window_idle_time(prog):
    whole = trace_summary(_profile("program_spans.pbtxt"))
    idle = whole.window_s - whole.busy_s
    assert prog.window_s == pytest.approx(whole.window_s)
    assert prog.idle_s == pytest.approx(idle)
    assert sum(prog.idle_by_span.values()) == pytest.approx(idle)


def test_host_ms_per_sync_reads_its_hand_computed_value(prog):
    # (9800 - 6000) and (7800 - 3500) ns; the third step leaves the window
    assert prog.host_ms_per_sync == pytest.approx(1e-6 * (3800 + 4300) / 2)


def test_innermost_of_spans_begun_together_is_the_one_ending_first():
    a = spans.Span("bench.step", 0, 10)
    b = spans.Span("serve.step", 0, 9)
    c = spans.Span("serve.fill", 0, 4)
    pieces = spans.innermost([a, b, c], 0, 12)
    assert pieces == [(0, 4, "serve.fill"), (4, 9, "serve.step"),
                      (9, 10, "bench.step"), (10, 12, spans.NO_SPAN)]


def _write_xplane(directory, name):
    """The trace as the profiler leaves it under its output directory."""
    from jax.profiler import ProfileData
    out = directory / "plugins" / "profile" / "1"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace((DATA / name).read_text()))


def _reader_on(tmp_path, monkeypatch, name):
    _write_xplane(tmp_path / "trace", name)
    monkeypatch.setattr(run, "OUT", tmp_path)
    view = run.RunView(spec={}, seconds=1.0, setup_s=0.0, recs=[], steps=[],
                       counters=None, trace=trace_summary(_profile(name)),
                       kernel_ops={}, max_batch=1, span=1, device_kind="",
                       profile=(0.0, 1.0))
    return run.reader("host_ms_per_sync")(view)


def test_host_ms_per_sync_reader_reads_the_run_trace(tmp_path, monkeypatch):
    got = _reader_on(tmp_path, monkeypatch, "program_spans.pbtxt")
    assert got == pytest.approx(0.00405)


def test_host_ms_per_sync_reader_gives_nothing_without_program_spans(
        tmp_path, monkeypatch):
    assert _reader_on(tmp_path, monkeypatch, "small_trace.pbtxt") is None


def test_cli_prints_one_json_line(tmp_path, capsys):
    import json
    _write_xplane(tmp_path, "program_spans.pbtxt")
    assert spans.main([str(tmp_path)]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["host_ms_per_sync"] == pytest.approx(0.00405)
    assert list(line["idle_by_span"])[0] == "serve.fold"
    assert line["span_ms"]["serve.step"][0] == 3
