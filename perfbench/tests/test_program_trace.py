"""The serving program's marks in a trace (``harness/program_trace.py``)
and the per-layer metrics that read them: on hand-made records, and on a
small serving trace recorded on a v5e
(``perfbench/tools/record_serve_trace.py``)."""

from pathlib import Path

import pytest

from harness import program_trace, registry, trace

DATA = Path(__file__).resolve().parent / "data"
READERS = ("head_ms", "sample_ms", "prefill_ms", "kv_page_fill",
           "head_ms.burst", "sample_ms.burst", "prefill_ms.burst")


def _records():
    """One decoding round: the decode step's module and the head's, a
    prefill, and the program's spans nested inside the runners'
    (``bench.sample`` wraps ``serve.sample``); one admission span lies
    after the window."""
    ops = [("fusion.1", 1000, 2000, False, "jit_decode_step/fusion"),
           ("while.2", 2500, 1500, False, "jit_decode_step/while"),
           ("custom-call.3", 4000, 1000, True,
            "jit_head_decode/maple_spmm_compact"),
           ("fusion.4", 4500, 1000, False, "jit_head_decode/fusion"),
           ("fusion.5", 9500, 300, False, "jit_prefill/fusion"),
           ("copy.6", 8000, 500, False, "copy")]          # no module
    host = [("bench.window", 0, 10000),
            ("bench.step", 500, 9000),
            ("bench.sample", 6000, 1500)]
    serve = [("serve.round", 600, 8800, {"round": 0}),
             ("serve.decode", 700, 200,
              {"live": 2, "pages_in_use": 6, "pages_free": 26}),
             ("serve.fetch", 900, 5000, {}),
             ("serve.sample", 6100, 1300, {"rid": 4}),
             ("serve.sample.draw", 6200, 1000, {}),
             ("serve.sample", 8600, 500, {"rid": 5}),
             ("serve.admit", 10500, 600, {"rid": 6})]    # after the window
    return {"devices": {"/device:TPU:0": ops}, "host": host, "serve": serve}


def test_module_seconds_union_inside_the_window():
    r = program_trace.reduce(_records())
    m = r["module_s"]
    assert m == pytest.approx({"jit_decode_step": 3000e-9,     # 1000-4000
                               "jit_head_decode": 1500e-9,     # 4000-5500
                               "jit_prefill": 300e-9})
    assert list(m)[0] == "jit_decode_step"
    # every busy moment but the op outside any module
    assert r["busy_s"] == pytest.approx(trace.reduce(_records())["busy_s"])
    assert sum(m.values()) == pytest.approx(r["busy_s"] - 500e-9)


def test_span_totals_inside_the_window():
    s = program_trace.reduce(_records())["spans"]
    assert s["serve.sample"]["n"] == 2
    assert s["serve.sample"]["s"] == pytest.approx(1800e-9)
    assert s["serve.sample"]["sum"] == {"rid": 9}
    assert s["serve.decode"]["sum"] == {"live": 2, "pages_in_use": 6,
                                        "pages_free": 26}
    assert s["serve.round"]["n"] == 1
    assert "serve.admit" not in s


def test_idle_gaps_go_to_the_innermost_span():
    r = program_trace.reduce(_records())
    # gaps [0,1000]: unmarked to 500, bench.step, serve.round, decode
    # 700-900, fetch; [5500,8000]: fetch to 5900, round, bench.sample
    # 6000-6100, sample, draw 6200-7200, sample, bench.sample 7400-7500,
    # round; [8500,9500]: round, sample 8600-9100, round to 9400,
    # bench.step; [9800,10000]: unmarked
    assert dict(r["idle_gaps"]) == pytest.approx({
        "host.unmarked": 700e-9, "bench.step": 200e-9,
        "serve.round": 1100e-9, "serve.decode": 200e-9,
        "serve.fetch": 500e-9, "bench.sample": 200e-9,
        "serve.sample": 800e-9, "serve.sample.draw": 1000e-9})
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_innermost_agrees_with_trace_reduce_on_few_spans():
    rec = dict(_records(), serve=[])
    old = dict(trace.reduce(rec)["breakdown"]["idle_gaps"])
    assert dict(program_trace.reduce(rec)["idle_gaps"]) == pytest.approx(old)


def test_a_long_span_covers_many_short_ones():
    """A span that opened before a hundred shorter ones still covers the
    gap after them."""
    spans = [("bench.step", 0, 100000)]
    spans += [("serve.sample", 10 + 10 * i, 5) for i in range(100)]
    gaps = program_trace.innermost([(10, 99000)], spans)
    assert gaps == pytest.approx({"serve.sample": 500e-9,
                                  "bench.step": 98990e-9 - 500e-9})


def test_no_device_reads_nothing():
    assert program_trace.reduce({"devices": {}, "host": [],
                                 "serve": []}) is None


def _ctx(monkeypatch, reduced):
    monkeypatch.setattr(program_trace, "reading", lambda ctx: reduced)
    return {m: registry.metric_reader(m).read({}) for m in READERS}


def test_readers_on_hand_made_records(monkeypatch):
    read = _ctx(monkeypatch, program_trace.reduce(_records()))
    assert read["head_ms"] == pytest.approx(1500e-9 * 1000.0)
    assert read["sample_ms"] == pytest.approx(1800e-9 * 1000.0)
    assert read["prefill_ms"] is None          # no admission in the window
    assert read["kv_page_fill"] == pytest.approx(100.0 * 6 / 32)
    for m in ("head_ms", "sample_ms", "prefill_ms"):
        assert read[m + ".burst"] == read[m]


def test_readers_silent_without_the_program_s_marks(monkeypatch):
    """A program that leaves no ``serve.*`` span and no named module (the
    parent of this metric's first PR): nothing is read, nothing raised."""
    bare = program_trace.reduce(dict(_records(), serve=[]))
    bare["module_s"] = {"jit__lambda": 1.0, "jit__unknown": 2.0}
    assert set(_ctx(monkeypatch, bare).values()) == {None}
    assert set(_ctx(monkeypatch, None).values()) == {None}


def test_reading_only_in_traced_serving_runs(tmp_path, monkeypatch):
    monkeypatch.setattr(program_trace, "ROOT", str(tmp_path))
    assert program_trace.reading({"kind": "serve", "trace": None}) is None
    assert program_trace.reading({"kind": "head_vjp",
                                  "trace": {"busy_s": 1.0}}) is None
    # traced, but no trace file where run.py leaves it
    assert program_trace.reading({"kind": "serve", "cell": "x.chat",
                                  "trace": {"busy_s": 1.0}}) is None


# --------------------------------------------------------------------------
# a serving trace recorded on a v5e: two requests of 16 tokens, 3 tokens
# each, through the smoke-size qwen3-4b cell (two rounds that decoded,
# two admissions), trimmed to what the reductions read
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    path = str(DATA / "small_serve_trace.xplane.pb")
    return path, program_trace.reduce(program_trace.load(path), top=100)


def test_recorded_serving_programs_and_spans(recorded):
    _, r = recorded
    m = r["module_s"]
    for name in ("jit_decode_step", "jit_head_decode", "jit_prefill",
                 "jit_head_prefill"):
        assert m[name] > 0, name
    assert not any(k in m for k in ("jit__lambda", "jit__unknown"))
    assert m["jit_head_decode"] == pytest.approx(2.9088e-05)
    assert sum(m.values()) == pytest.approx(r["busy_s"])
    s = r["spans"]
    assert {k: v["n"] for k, v in s.items()} == {
        "serve.round": 2, "serve.admit": 2, "serve.prefill": 2,
        "serve.head": 4, "serve.fetch": 4, "serve.prepare": 2,
        "serve.decode": 2, "serve.sample": 6, "serve.sample.check": 6,
        "serve.sample.draw": 6}
    assert s["serve.decode"]["sum"] == {"live": 4, "pages_in_use": 8,
                                        "pages_free": 24}
    assert s["serve.prefill"]["sum"]["padded_len"] == 32
    gaps = dict(r["idle_gaps"])
    assert max(gaps, key=gaps.get) == "serve.sample.draw"
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])


def test_recorded_trace_reads_as_before(recorded):
    """``trace.reduce`` reads the same busy time and window from it as
    the program's reduction; its breakdown sees only the runners'
    spans."""
    path, r = recorded
    old = trace.reduce(trace.load(path))
    assert old["busy_s"] == pytest.approx(r["busy_s"])
    assert old["window_s"] == pytest.approx(r["window_s"])
    assert old["kernel_s"] > 0
    assert {n for n, _ in old["breakdown"]["idle_gaps"]} <= {
        "bench.admit", "bench.sample", "bench.step", "bench.decode",
        "bench.head", "host.unmarked"}


def test_readers_on_the_recorded_trace(recorded, monkeypatch):
    path, r = recorded
    monkeypatch.setattr(program_trace, "trace_file", lambda ctx: path)
    ctx = {"kind": "serve", "cell": "qwen3-4b.chat", "trace": {"busy_s": 1}}
    read = {m: registry.metric_reader(m).read(ctx) for m in READERS}
    assert read["head_ms"] == pytest.approx(1000.0 * 2.9088e-05 / 2)
    assert read["sample_ms"] == pytest.approx(
        1000.0 * r["spans"]["serve.sample"]["s"] / 2)
    assert read["prefill_ms"] == pytest.approx(1000.0 * (
        r["module_s"]["jit_prefill"] + r["module_s"]["jit_head_prefill"]) / 2)
    assert read["kv_page_fill"] == pytest.approx(25.0)
    assert all(isinstance(v, float) for v in read.values())
